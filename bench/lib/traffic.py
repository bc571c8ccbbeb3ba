"""One general generator for every traffic mix: lengths, tokens, the loop.

A mix is a data file (``bench/traffic/<name>.json``).  Its lengths follow
the laws it names, taken at a fixed low-discrepancy sequence of quantiles
and clipped to the context (an output never runs past the context that its
prompt leaves): request i of every run has the same prompt and output
length, whatever the seed.  So a seed never changes how much work a run
holds or where it falls (dealing the same lengths to the clients in a
seeded order moved ``out_tok_s`` by 17% from seed to seed, against 0-4%
between two runs of one seed); the seed draws the token ids and, elsewhere,
the weights.

Closed loop: ``clients`` clients, client c sending its first request at
tick ``c * stagger_ticks / clients`` and each next one as soon as its last
one finished.  The engine is deterministic in ticks (one batched decode
step each), so a run's schedule of admissions, prefills and finishes
is the same on every run and every seed; only the seconds each tick
takes vary.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_PHI = (math.sqrt(5.0) - 1.0) / 2.0          # golden-ratio sequences
_PSI = math.sqrt(2.0) - 1.0


def _quantile(law: dict, u: float) -> int:
    if law["law"] != "lognormal":
        raise SystemExit(f"bench: unknown length law {law['law']!r}")
    z = NormalDist().inv_cdf(min(max(u, 1e-9), 1 - 1e-9))
    n = round(law["median"] * math.exp(law["sigma"] * z))
    return int(min(max(n, law["min"]), law["max"]))


def lengths(mix: dict, i: int, context: int) -> tuple[int, int]:
    """(prompt, output) length of the run's i-th request."""
    up = ((i + 1) * _PHI) % 1.0
    uo = ((i + 1) * _PSI + 0.5) % 1.0
    p = _quantile(mix["prompt_tokens"], up)
    return p, min(_quantile(mix["output_tokens"], uo), context - p)


def prompt_tokens(seed: int, i: int, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, i]).integers(
        0, vocab, n).astype(np.int32)


class ClosedLoop:
    """Clients of a closed loop, driven through the engine's hooks.

    ``poll`` (the engine calls it once per loop iteration, before admission)
    submits what is due; ``on_token`` and ``on_finish`` stamp the host clock
    on every delivered token and every finish.  ``log[uid]`` keeps, per
    request: prompt length, output length, submit time and tick, token
    times and ids, finish time and tick.  A tick is the engine's count of
    decode steps when the event happened.
    """

    def __init__(self, engine, mix: dict, seed: int, vocab: int,
                 clock, pack: int, context: int):
        from repro.serve import Request
        self._request = Request
        self.engine, self.mix, self.seed, self.vocab = engine, mix, seed, vocab
        self.clock, self.pack, self.context = clock, pack, context
        n = mix["clients"]
        self.ready = [(c * mix["stagger_ticks"]) // n for c in range(n)]
        self.client_of: dict[int, int] = {}
        self.next_uid = 0
        self.log: dict[int, dict] = {}
        self.finished = 0
        self.failed = 0

    def tick(self) -> int:
        return self.engine.stats.decode_steps

    def poll(self) -> int:
        """Submit every request that is due; returns how many."""
        now_tick = self.tick()
        sent = 0
        for c, due in enumerate(self.ready):
            if due is None or due > now_tick:
                continue
            uid = self.next_uid
            self.next_uid += 1
            p, o = lengths(self.mix, uid, self.context)
            req = self._request(
                uid=uid, prompt=prompt_tokens(self.seed, uid, p, self.vocab),
                max_new_tokens=o, temperature=0.0, eos_id=None,
                arrival=self.engine.vtime)
            t = self.clock()
            try:
                self.engine.submit(req)
            except ValueError:
                self.failed += 1
                continue
            self.ready[c] = None
            self.client_of[uid] = c
            self.log[uid] = {"prompt": req.prompt, "prompt_len": p,
                             "out_len": o, "submit_t": t,
                             "submit_tick": now_tick, "times": [],
                             "tokens": [], "finish_t": None,
                             "finish_tick": None}
            sent += 1
        return sent

    def on_token(self, uid: int, tok: int) -> None:
        rec = self.log[uid]
        rec["times"].append(self.clock())
        rec["tokens"].append(int(tok))

    def on_finish(self, result) -> None:
        rec = self.log[result.uid]
        rec["finish_t"] = self.clock()
        rec["finish_tick"] = self.tick()
        self.finished += 1
        self.ready[self.client_of.pop(result.uid)] = self.tick()
        self.engine.pop_result(result.uid)

    def start_pos(self, rec: dict) -> int:
        """Position of the request's first decode-step input."""
        p = rec["prompt_len"]
        prefix = p // self.pack * self.pack
        return p if prefix == p else prefix

    def active_positions(self, tick: int):
        """Positions of the requests that the decode step numbered `tick`
        (the count of steps before it) processes, one per active slot."""
        out = []
        for rec in self.log.values():
            a, f = rec["submit_tick"], rec["finish_tick"]
            if a <= tick and (f is None or tick < f):
                out.append(self.start_pos(rec) + tick - a)
        return out

"""How ``correct`` is decided for a served model.

Once the window has closed, the peak memory has been read and the program's
state is freed, the plain reference (the configuration's model module,
``bench/models/<model>.py``) reads these numbers, each against its limit
in ``bench/limits/<workload>.json`` (a number the file does not name is not
compared):

- ``kv0_rel_err`` (``cache_error``): the largest, over the filled ring
  positions of every slot in use at the close, of how far the first
  layer's cached K and V lie from the reference's K and V of the same token
  at the same position, after the directions that a DAS near tie leaves
  free are projected out.  A position's first-layer K and V depend on its
  own token only, so the reading carries no cascade through the layers.
  It covers the packed prefill, the tail feed and the decode writes, the
  ring's placement and wrap, the norm, DAS, the ternary GEMM and RoPE.
- ``max_token_rank`` (``compare``): over a seeded sample of the requests
  the run finished, always holding the longest, the worst rank of a served
  token among the reference's logits at its position (1 = the reference's
  best), the reference run once over each prompt and its served tokens.
  It covers every layer, the head and the sampler, but only where rounding
  cannot reorder the logits: at bf16 a DAS near tie broken the other way
  in any layer moves the deeper layers as far as the fp8 control does, so
  no chip cell compares it; the CPU test cell (f32) does.
- ``wrong_length_requests``: requests that did not emit exactly their drawn
  output length (limit 0).

``judge`` decides: every number named in the limits file is present and
within its limit.  The control (the reference one precision below the
configuration's, in the program's place) and the faults are judged by the
same function on the same sample.
"""

from __future__ import annotations

import numpy as np

# the control: the reference in the program's place, one precision below
# the configuration's own
CONTROL = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}

def sample(log: dict, t1: float, seed: int, want: dict) -> list:
    """uids of requests the run finished by the window's close t1: the
    longest (prompt + output) first, then others in a seeded order until
    the sample holds `want["requests"]` requests and `want["min_tokens"]`
    served tokens."""
    done = [u for u, r in log.items()
            if r["finish_t"] is not None and r["finish_t"] <= t1]
    if not done:
        return []
    longest = max(done, key=lambda u: (log[u]["prompt_len"]
                                       + log[u]["out_len"], -u))
    rest = [u for u in done if u != longest]
    rng = np.random.default_rng([seed, 7])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, served = [longest], len(log[longest]["tokens"])
    for u in rest:
        if len(out) >= want["requests"] and served >= want["min_tokens"]:
            break
        out.append(u)
        served += len(log[u]["tokens"])
    return out


def ranks(model, key, cfg: dict, rec: dict, pad_to: int,
          act_dtype: str | None = None) -> np.ndarray:
    """Per served token, its rank among the reference's logits at its
    position (1 = the reference's best).  With `act_dtype` (the control)
    the token ranked is the one the lower-precision pass puts first at that
    position, over the same prompt and served tokens."""
    prompt, served = np.asarray(rec["prompt"]), np.asarray(rec["tokens"])
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    ref = model.logits(key, cfg, seq, pad_to)[len(prompt) - 1:]
    if act_dtype is not None:
        low = model.logits(key, cfg, seq, pad_to, act_dtype)[len(prompt) - 1:]
        served = low.argmax(-1)
    mine = ref[np.arange(len(served)), served]
    return (ref > mine[:, None]).sum(-1) + 1


def compare(model, key, cfg: dict, log: dict, uids: list, pad_to: int,
            act_dtype: str | None = None) -> dict:
    r = [ranks(model, key, cfg, log[u], pad_to, act_dtype) for u in uids]
    flat = np.concatenate(r) if r else np.zeros(0, int)
    return {"max_token_rank": int(flat.max()) if flat.size else None,
            "requests": len(uids), "tokens": int(flat.size),
            "longest": max((log[u]["prompt_len"] + len(log[u]["tokens"])
                            for u in uids), default=0)}


def first_layer_cache(engine) -> dict:
    """The first layer's ring cache as the window left it, on the host:
    k, v (slots, ring, kv heads, head dim) and pos (slots, ring), -1 where
    a ring slot holds nothing (``models/kvcache.py``'s ring layout)."""
    import jax
    c = engine.caches
    layer = c["stacked"][0] if c["stacked"] else c["tail"][0]
    first = (lambda a: a[0]) if c["stacked"] else (lambda a: a)
    return {k: np.asarray(jax.device_get(first(layer[k])), np.float32)
            for k in ("k", "v", "pos")}


def cache_error(model, key, cfg: dict, loop, snap: dict, close_tick: int,
                act_dtype: str | None = None) -> dict:
    """``kv0_rel_err``: the largest over every filled ring position of the
    slots in use at the close of ``model.first_layer_error``.  A slot is
    matched to the request whose last written position it holds (the best
    matching one, should two requests share it); a slot that matches no
    request reads infinity.  With `act_dtype` (the control) the K and V
    compared are the reference's at that precision, not the cache's."""
    active = [r for r in loop.log.values() if r["finish_t"] is None]
    per_slot, free = [], []
    for b in range(snap["pos"].shape[0]):
        pos = snap["pos"][b].astype(np.int64)
        filled = pos >= 0
        if not filled.any():
            continue
        last = pos.max()
        best = None
        for r in active:
            if loop.start_pos(r) + close_tick - r["submit_tick"] - 1 != last:
                continue
            seq = np.concatenate([np.asarray(r["prompt"]),
                                  np.asarray(r["tokens"], np.int32)])
            p = np.where(filled, pos, 0)
            if act_dtype is None:
                k, v = snap["k"][b], snap["v"][b]
            else:
                k, v = model.first_layer_kv(key, cfg, seq[p], p, act_dtype)
            err, nfree = model.first_layer_error(key, cfg, seq[p], p, k, v)
            err, nfree = err[filled], nfree[filled]
            if best is None or err.max() < best[0].max():
                best = (err, nfree)
        per_slot.append(None if best is None else best[0])
        if best is not None:
            free.append(best[1])
    if not per_slot:
        return {"kv0_rel_err": None, "slots": 0}
    if any(e is None for e in per_slot):
        return {"kv0_rel_err": float("inf"), "slots": len(per_slot)}
    pooled, free = np.concatenate(per_slot), np.concatenate(free)
    return {"kv0_rel_err": float(pooled.max()),
            "kv0_q10_q50_q90_max": np.quantile(
                pooled, [0.1, 0.5, 0.9, 1.0]).tolist(),
            "near_tie_lanes_mean_max": [float(free.mean()), int(free.max())],
            "slots": len(per_slot), "positions": int(pooled.size)}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number the limits file
    names must be read and lie within its limit."""
    checks = {n: {"value": readings.get(n), "limit": lim}
              for n, lim in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def read(model, key, cfg: dict, loop, snap: dict, close_tick: int,
         uids: list, pad_to: int, limits: dict,
         act_dtype: str | None = None) -> dict:
    """Every number the limits file names, for the program (or, with
    `act_dtype`, the control in its place), and the sample's sizes."""
    out: dict = {}
    if "max_token_rank" in limits:
        out.update(compare(model, key, cfg, loop.log, uids, pad_to,
                           act_dtype))
    if "kv0_rel_err" in limits:
        out.update(cache_error(model, key, cfg, loop, snap, close_tick,
                               act_dtype))
    out["wrong_length_requests"] = 0 if act_dtype else sum(
        1 for r in loop.log.values()
        if r["finish_t"] is not None and len(r["tokens"]) != r["out_len"])
    return out

"""End-to-end metrics of one measured window, from the clients' token log.

Every time here is the host clock the clients stamped (``traffic.py``):
submission, each delivered token, each finish.  A metric is taken over all
the work and all the time of the window [t0, t1].
"""

from __future__ import annotations

import statistics


def out_tokens(log: dict, t0: float, t1: float) -> int:
    return sum(t0 <= t <= t1 for r in log.values() for t in r["times"])


def out_tok_s(log: dict, t0: float, t1: float) -> float:
    """Tokens delivered in the window / the window's seconds."""
    return out_tokens(log, t0, t1) / (t1 - t0)


def gaps_ending_in(log: dict, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one request whose later token
    lands in the window (seconds)."""
    out = []
    for r in log.values():
        ts = r["times"]
        out.extend(b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1)
    return out


def first_token_waits(log: dict, t0: float, t1: float) -> list[float]:
    """Submission to first token, for every request whose first token lands
    in the window (seconds)."""
    return [r["times"][0] - r["submit_t"] for r in log.values()
            if r["times"] and t0 <= r["times"][0] <= t1]


def percentile(xs: list[float], q: float) -> float | None:
    """The q-th percentile (0 < q < 100) of xs, interpolated between order
    statistics (Python's ``statistics.quantiles``, inclusive method)."""
    if len(xs) < 2:
        return xs[0] if xs else None
    return statistics.quantiles(xs, n=100, method="inclusive")[round(q) - 1]


def metrics(log: dict, t0: float, t1: float) -> dict:
    """{name: (value, unit, samples)} for every end-to-end metric the log
    can give (setup_s is the harness's)."""
    g = gaps_ending_in(log, t0, t1)
    w = first_token_waits(log, t0, t1)
    p99 = percentile(g, 99)
    return {
        "out_tok_s": (out_tok_s(log, t0, t1), "tokens/s",
                      out_tokens(log, t0, t1)),
        "itl_p99_ms": (None if p99 is None else 1e3 * p99, "ms", len(g)),
        "ttft_p50_s": (statistics.median(w) if w else None, "s", len(w)),
    }

"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes one ``.xplane.pb`` per trace.  Device planes
(``/device:TPU:<n>``) carry a line of XLA operations and a line of XLA
modules (one event per run of an executable); the host plane carries the
harness's own spans (``bench.*``, written with ``TraceAnnotation``) on the
same clock.  The window is the harness span ``bench.traced``.

Kernel and executable names are matched by the patterns in
``kernels.json``, so a change that renames one adds a pattern, not code.
"""

from __future__ import annotations

import glob
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."


def patterns() -> dict:
    with open(os.path.join(os.path.dirname(__file__), "kernels.json")) as f:
        return json.load(f)


def matches(name: str, pats: list[str]) -> bool:
    return any(re.search(p, name) for p in pats)


class Trace:
    """Events of one trace, in seconds on one clock.

    ``ops`` / ``modules``: per device, lists of (name, start, end);
    ``spans``: host spans (name, start, end) whose name starts ``bench.``;
    ``window``: (start, end) of the traced window."""

    def __init__(self, ops: list, modules: list, spans: list, window):
        self.ops, self.modules, self.spans = ops, modules, spans
        self.window = window

    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise SystemExit(f"bench: no trace was written under {path}")
        pd = ProfileData.from_file(files[-1])
        ops, modules, spans = [], [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                o, m = [], []
                for line in plane.lines:
                    dst = {OPS_LINE: o, MODULES_LINE: m}.get(line.name)
                    if dst is not None:
                        dst.extend((e.name, e.start_ns * 1e-9,
                                    e.end_ns * 1e-9) for e in line.events)
                if o or m:
                    ops.append(o)
                    modules.append(m)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        window = (win[0][1], win[0][2]) if win else None
        return cls(ops or [[]], modules or [[]], spans, window)

    # -- reductions -------------------------------------------------------

    def clipped(self, events: list) -> list:
        lo, hi = self.window
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events
                if b > lo and a < hi]

    def busy_s(self, device: int = 0) -> float:
        """Length of the union of the device's operation intervals inside
        the window."""
        return sum(b - a for a, b in union(
            [(a, b) for _, a, b in self.clipped(self.ops[device])]))

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def time_of(self, pats: list[str], device: int = 0,
                modules: bool = False) -> tuple[float, int]:
        """(seconds, count) of the events matching `pats` in the window."""
        ev = self.clipped((self.modules if modules else self.ops)[device])
        hit = [b - a for n, a, b in ev if matches(n, pats)]
        return sum(hit), len(hit)

    def module_time_except(self, pats: list[str], device: int = 0) -> float:
        ev = self.clipped(self.modules[device])
        return sum(b - a for n, a, b in ev if not matches(n, pats))

    def top_ops(self, n: int = 10, device: int = 0) -> list:
        """The n operations with the most self time in the window (an
        operation's time less that of the operations nested in it, such as
        a loop's body), each named by its HLO instruction name."""
        tot: dict = {}
        for name, t in self_times(self.clipped(self.ops[device])):
            key = name.split(" = ")[0]
            tot[key] = tot.get(key, 0.0) + t
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, device: int = 0) -> list:
        """The n longest stretches of the window with no device operation,
        each named by the harness span the host was in at its middle
        (innermost span wins; "other" where the host was in none)."""
        lo, hi = self.window
        busy = union([(a, b) for _, a, b in self.clipped(self.ops[device])])
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_label((a + b) / 2), b - a] for a, b in gaps[:n]]

    def host_label(self, t: float) -> str:
        inner = [(b - a, n) for n, a, b in self.spans
                 if a <= t <= b and n != WINDOW_SPAN]
        if not inner:
            return "other"
        return min(inner)[1][len(SPAN_PREFIX):]


def self_times(events: list) -> list:
    """(name, self seconds) of properly nested (name, start, end) events:
    each event's length less the lengths of its direct children."""
    out, stack = [], []            # stack of [name, start, end, child_time]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, s0, e0, c = stack.pop()
            out.append((n, e0 - s0 - c))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    out.extend((n, e0 - s0 - c) for n, s0, e0, c in stack)
    return out


def union(intervals: list) -> list:
    """Sorted, merged copy of (start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out

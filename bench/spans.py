"""The program's own host spans in a traced window, for the readers of its
phases.

``fed.run_mesh`` and ``train.trainer.train`` open named spans through
``repro.obs.profile`` at their phase boundaries (``run_mesh/round``,
``run_mesh/sync``, ``train/data``, ...; docs/observability.md lists them).
They land on the profiler's host plane, on the clock of the device's
operations, with their integer stats (``reads``, ``step_num``). A program
that opens none gives readers nothing to find, and they return ``None``.
"""
from __future__ import annotations

import bisect
import math

from bench import tracing


def find(trace: tracing.Reduction, name: str) -> list:
    """The host spans called ``name`` that lie wholly inside the window,
    in order of start."""
    return sorted((e for e in trace.events
                   if e.name == name
                   and not e.plane.startswith(tracing.DEVICE_PREFIX)
                   and e.start_ns >= trace.t0 and e.end_ns <= trace.t1),
                  key=lambda e: e.start_ns)


class Cover:
    """The union of some intervals, asked how much of ``[s, e)`` it
    covers."""

    def __init__(self, intervals):
        merged: list = []
        for s, e in sorted(intervals):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        self.merged = merged
        self.starts = [s for s, _ in merged]

    def within(self, s: float, e: float) -> float:
        i = max(bisect.bisect_right(self.starts, s) - 1, 0)
        total = 0.0
        while i < len(self.merged) and self.merged[i][0] < e:
            a, b = self.merged[i]
            total += max(0.0, min(b, e) - max(a, s))
            i += 1
        return total


def self_ns(outers: list, inners: list) -> list:
    """Each of ``outers``' length less the part that ``inners`` cover."""
    cover = Cover((e.start_ns, e.end_ns) for e in inners)
    return [o.dur_ns - cover.within(o.start_ns, o.end_ns) for o in outers]


def idle_ns(trace: tracing.Reduction, spans: list) -> float:
    """The first chip's idle time under ``spans`` (disjoint, inside the
    window): their length less the union of that chip's operations within
    them."""
    planes = trace.device_planes()
    ops = trace.ops(planes[0]) if planes else []
    busy = Cover((e.start_ns, e.end_ns) for e in ops)
    return sum(s.dur_ns - busy.within(s.start_ns, s.end_ns) for s in spans)


def call_setup_s(trace: tracing.Reduction, call: str, dispatch: str):
    """From the start of the window's ``call`` span to the end of the first
    ``dispatch`` span inside it, in seconds; ``None`` without them."""
    calls = find(trace, call)
    if not calls:
        return None
    first = [d for d in find(trace, dispatch)
             if calls[0].start_ns <= d.start_ns < calls[0].end_ns]
    if not first:
        return None
    return (first[0].end_ns - calls[0].start_ns) * 1e-9


def percentile(values: list, q: float) -> float:
    """The nearest-rank ``q``-th percentile of ``values``."""
    ranked = sorted(values)
    return ranked[max(math.ceil(q / 100.0 * len(ranked)), 1) - 1]

"""Profiler trace of the measured window, and its reduction to numbers.

The window runs under a ``bench_window`` host annotation. The reduction
reads the device planes (``/device:TPU:<n>``), takes the operations on each
plane's ``XLA Ops`` line, and clips them to that annotation: busy time is
the union of their intervals, the window is the annotation's length.

``Reduction`` works on plain event lists, so the tests check it on
hand-made events.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from typing import Optional

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _union(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Reduction:
    def __init__(self, events: list):
        self.events = events
        wins = [e for e in events if e.name == WINDOW]
        if not wins:
            raise ValueError(f"trace holds no {WINDOW!r} annotation")
        w = max(wins, key=lambda e: e.dur_ns)
        self.t0, self.t1 = w.start_ns, w.end_ns

    # ---------------------------------------------------------- selection
    def device_planes(self) -> list:
        names = {e.plane for e in self.events
                 if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE}
        return sorted(names, key=lambda p: int(p[len(DEVICE_PREFIX):]
                                               .split()[0]))

    def ops(self, plane: Optional[str] = None) -> list:
        """Device operations inside the window (all planes, or one)."""
        return [e for e in self.events
                if e.line == OPS_LINE and e.plane.startswith(DEVICE_PREFIX)
                and (plane is None or e.plane == plane)
                and e.end_ns > self.t0 and e.start_ns < self.t1]

    def _clip(self, e) -> tuple:
        return max(e.start_ns, self.t0), min(e.end_ns, self.t1)

    def _inside_ns(self, e) -> float:
        s, t = self._clip(e)
        return t - s

    # ------------------------------------------------------------- numbers
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self, plane: str) -> float:
        return _union(self._clip(e) for e in self.ops(plane)) * 1e-9

    def mean_busy_s(self, chips: int) -> float:
        planes = self.device_planes()[:chips]
        if not planes:
            return 0.0
        return sum(self.busy_s(p) for p in planes) / len(planes)

    def op_seconds(self, pred, chips: Optional[int] = None) -> float:
        planes = set(self.device_planes()[:chips] if chips else
                     self.device_planes())
        return sum(self._inside_ns(e) for e in self.ops()
                   if e.plane in planes and pred(e)) * 1e-9

    def top_ops(self, n: int = 10, chips: int = 1) -> list:
        planes = set(self.device_planes()[:chips])
        tot = collections.Counter()
        for e in self.ops():
            if e.plane in planes:
                tot[e.name] += self._inside_ns(e) * 1e-9 / len(planes)
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest gaps between operations on the first device, named
        by the innermost host event (a runtime call such as a dispatch or a
        read back to the host) that covers each gap's middle."""
        planes = self.device_planes()
        if not planes:
            return []
        spans = sorted(self._clip(e) for e in self.ops(planes[0]))
        gaps, cur = [], self.t0
        for s, e in spans:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        host = [e for e in self.events if not e.plane.startswith(
            DEVICE_PREFIX) and e.name != WINDOW]
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            cover = [h for h in host if h.start_ns <= mid < h.end_ns]
            name = min(cover, key=lambda h: h.dur_ns).name if cover \
                else "(no host event)"
            out.append([name, (e - s) * 1e-9])
        return out

    def device_summary(self, chips: int) -> dict:
        return {"busy_s": self.mean_busy_s(chips),
                "window_s": self.window_s,
                "breakdown": {"device_ops": self.top_ops(10, chips),
                              "idle_gaps": self.idle_gaps(10)}}


_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1}
_SHAPE = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([0-9,]*)\]")


def _shapes(text: str) -> list:
    return [(tuple(int(d) for d in dims.split(",") if d), _ITEMSIZE[dt])
            for dt, dims in _SHAPE.findall(text)]


def _bracketed(text: str, opener: str) -> str:
    """What follows ``opener`` up to the bracket that closes it."""
    i = text.find(opener)
    if i < 0:
        return ""
    i += len(opener)
    close = {"(": ")", "{": "}"}[opener[-1]]
    depth = 1
    for j in range(i, len(text)):
        depth += {opener[-1]: 1, close: -1}.get(text[j], 0)
        if depth == 0:
            return text[i:j]
    return text[i:]


def kernel_call(e: Event):
    """``(kernel name, operand shapes, result shapes)`` of a Pallas kernel's
    device event, from its HLO text; ``None`` for any other operation.

    On a TPU a Pallas kernel is a ``tpu_custom_call`` whose instruction and
    ``op_name`` carry the repo's ``kernels/<name>`` scope; its operand
    shapes are in ``operand_layout_constraints`` (the instruction's own
    operand list names values, not shapes).
    """
    texts = [str(v) for v in e.stats.values()] + [e.name]
    joined = " ".join(texts)
    if "tpu_custom_call" not in joined and "/pallas_call" not in joined:
        return None
    # the scope's name where the HLO metadata is there, else the
    # instruction's own (a TPU trace names an op by its HLO text)
    m = re.search(r"kernels/(\w+)/pallas_call", joined) or \
        re.match(r"%?([^\s.=]+)", e.name)
    name = m.group(1)
    hlo = next((t for t in texts if " custom-call(" in t), "")
    result = hlo.partition(" custom-call(")[0].partition("=")[2]
    operands = _bracketed(hlo, "operand_layout_constraints={") or \
        _bracketed(hlo, " custom-call(")
    return name, _shapes(operands), _shapes(result)


def _trace_json_stats(xplane_path: str) -> dict:
    """Event name -> the args the profiler wrote for it into the
    ``.trace.json.gz`` beside the ``.xplane.pb``: these hold an operation's
    metadata stats (its HLO text), which ``ProfileData`` does not give."""
    found = glob.glob(os.path.join(os.path.dirname(xplane_path),
                                   "*.trace.json.gz"))
    if not found:
        return {}
    with gzip.open(found[0], "rt") as f:
        rows = json.load(f).get("traceEvents", [])
    out = {}
    for r in rows:
        if r.get("ph") == "X" and r.get("args") and r["name"] not in out:
            out[r["name"]] = r["args"]
    return out


def load_xplane(path: str) -> list:
    """Every event of an ``.xplane.pb`` file, with its stats and those of
    its metadata."""
    from jax.profiler import ProfileData

    extra = _trace_json_stats(path)
    pd = ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(extra.get(ev.name, {}))
                for k, v in ev.stats:
                    stats[k] = v if isinstance(v, (int, float, str)) \
                        else str(v)
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns),
                                    stats))
    return events


class Tracer:
    """A profiler session in a scratch directory under ``TMPDIR``.

    Python's own function calls are not traced: at some hundred thousand
    events a second they would slow the host loop being measured and make
    the trace too large to read within a run. The runtime's host events
    (dispatches, transfers, reads back to the host) stay.
    """

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def xplane_path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return found[0]

    def reduce(self) -> Reduction:
        return Reduction(load_xplane(self.xplane_path()))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)

"""The benchmark's harness: finds a cell's pieces by name, checks the device,
counts compilations, runs the cell's driver and prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json        sizes, source, cut and assumptions
  bench/configs/<config>.<part>.py   the configuration's code (task, ref)
  bench/traffic/<traffic>.json       the mix: its driver and parameters
  bench/drivers/<driver>.py          how to drive a program entry point
  bench/metrics/<metric>.py          a per-layer metric's reader

A driver exposes ``run(ctx) -> Outcome``; a reader exposes
``read(ctx, outcome, trace) -> float | None``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (wrong device, missing piece)."""


# ------------------------------------------------------------ the pieces
def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def load_json(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a file whose name may hold dots or dashes."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = "bench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.resolve()))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_part(config: str, part: str, bench_dir: Path = BENCH_DIR):
    """``bench/configs/<config>.<part>.py``: the task or reference code."""
    return load_module(bench_dir / "configs" / f"{config}.{part}.py")


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict            # the BENCHMARK.json workload entry
    config: dict
    traffic: dict
    end_to_end: list       # metric entries this cell reports
    per_layer: list
    bench_dir: Path = BENCH_DIR

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def find_cell(name: str, bench: Optional[dict] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]
    return Cell(name=name, entry=entry,
                config=load_json("configs", entry["config"], bench_dir),
                traffic=load_json("traffic", entry["traffic"], bench_dir),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                bench_dir=bench_dir)


def driver_for(cell: Cell):
    return load_module(cell.bench_dir / "drivers"
                       / f"{cell.traffic['driver']}.py")


def reader_for(metric: str, bench_dir: Path = BENCH_DIR):
    return load_module(bench_dir / "metrics" / f"{metric}.py")


# -------------------------------------------------------------- the device
def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json; "
                         "add its published peaks before measuring on it")
    return table[kind]


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or a ``BenchError`` naming what
    JAX found instead."""
    import jax

    devices = jax.devices()
    found = f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})"
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {found}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} TPU chips; JAX found {found}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def configure_jax(root: Path = ROOT) -> str:
    """f32 with x64 off, and the persistent compilation cache at a fixed
    path inside the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says),
    holding every program however fast it compiled."""
    import jax

    if jax.config.jax_enable_x64:
        raise BenchError("the benchmark runs in f32; unset JAX_ENABLE_X64")
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


class CompileCounter:
    """Counts XLA compilations that the persistent cache did not serve.

    Every executable JAX builds passes ``compile_or_get_cached``, which
    records ``backend_compile_duration`` for the request and ``cache_hits``
    when the cache served it; their difference is what compiled.
    """

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        self.missed: list = []          # names of the programs compiled
        self._hit = False               # the request in flight was a hit
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, _secs, fun_name=None, **_kw):
        # a request's hit, if any, is recorded before its duration
        if event == self.REQUEST:
            self.requests += 1
            if not self._hit:
                self.missed.append(str(fun_name))
            self._hit = False

    def _event(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1
            self._hit = True

    @property
    def compiled(self) -> int:
        return self.requests - self.hits

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.compiled


# ------------------------------------------------------------- the window
@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    counter: Any
    tracer: Any                     # bench.tracing.Tracer or None
    t_start: float = dataclasses.field(default_factory=time.perf_counter)

    def log(self, what: str, **numbers) -> None:
        print(f"[bench] {what} {json.dumps(numbers, default=float)}",
              file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit (passes when value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: set-up and window times, the work done,
    the comparison with the reference, and what the readers need."""
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    end_to_end: dict                # metric name -> value
    checks: list                    # [Check]
    work: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def entry_call(ctx: Context, fn: Callable[..., Any], *args,
               window: bool = False, **kwargs):
    """One call of the program's entry point, ``fn(*args, **kwargs)``;
    ``window=True`` makes it the measured one, under the compile counter
    (and the tracer).

    A driver makes every call, warm-up and window alike, through this
    function and from one line of its own. A Pallas kernel's compiled form
    carries the Python call stack it was traced from, so a window reached
    from other lines than its warm-up finds no compiled program in the
    cache and compiles inside the window.

    Returns ``(result, seconds)``; raises if the window compiled anything.
    """
    import jax

    before = ctx.counter.snapshot()
    if window and ctx.tracer is not None:
        ctx.tracer.start()
    span = jax.profiler.TraceAnnotation("bench_window") if window \
        else contextlib.nullcontext()
    t0 = time.perf_counter()
    with span:
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    seconds = time.perf_counter() - t0
    if not window:
        return out, seconds
    if ctx.tracer is not None:
        ctx.tracer.stop()
    requests, compiled = ctx.counter.snapshot()
    ctx.log("window_compiles", programs_requested=requests - before[0],
            compiled=compiled - before[1])
    if compiled != before[1]:
        raise BenchError(f"{compiled - before[1]} program(s) compiled inside "
                         "the measured window: "
                         f"{ctx.counter.missed[-(compiled - before[1]):]}")
    return out, seconds


def result_line(cell: Cell, out: Outcome, layer_values: Optional[dict],
                trace_info: Optional[dict]) -> dict:
    import jax

    dev = jax.devices()[0]
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if layer_values is None:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        names = [m["name"] for m in cell.end_to_end]
    else:
        values = layer_values
        names = [m["name"] for m in cell.per_layer]
    metrics = {n: {"value": values[n], "unit": units[n]}
               for n in names if values.get(n) is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if trace_info is not None:
        device["busy_s"] = trace_info["busy_s"]
        device["window_s"] = trace_info["window_s"]
        line["breakdown"] = trace_info["breakdown"]
    # a number that is not finite (a diverged run) goes out as null
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in out.checks}
    return line


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices: Optional[list] = None,
             config_overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line.

    ``devices`` skips the look for TPU chips (the tests pass CPU devices);
    the overrides shrink a configuration or mix for those tests.
    ``t_start`` is when set-up began (``time.perf_counter``), by default now.
    """
    cell = find_cell(name)
    if config_overrides:
        cell.config = {**cell.config, **config_overrides}
    if traffic_overrides:
        cell.traffic = {**cell.traffic, **traffic_overrides}
    configure_jax()
    if devices is None:
        devices = check_devices(cell.chips)
    counter = CompileCounter()
    tracer = None
    if trace:
        from bench import tracing
        tracer = tracing.Tracer()
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  devices=devices, counter=counter, tracer=tracer,
                  t_start=time.perf_counter() if t_start is None else t_start)
    try:
        out = driver_for(cell).run(ctx)
    finally:
        counter.close()
    layer_values = trace_info = None
    if trace:
        red = tracer.reduce()
        trace_info = red.device_summary(len(devices))
        layer_values = {}
        for m in cell.per_layer:
            v = reader_for(m["name"]).read(ctx, out, red)
            if v is not None:
                layer_values[m["name"]] = float(v)
        tracer.close()
    for c in out.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return result_line(cell, out, layer_values, trace_info)

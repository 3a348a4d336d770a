"""repro.obs — in-graph telemetry + host-side observability sinks.

Two halves, one package:

  * **In-graph** (``obs.metrics``): the ``MetricBag`` — a flat pytree of
    named scalar observables that rides alongside the optimizer state
    through every execution surface (``simulator.trajectory``'s scan, the
    sweep engine's ``lax.map`` partitions, the ``repro.fed`` event loop)
    without perturbing it: metrics-on runs are bit-identical to
    metrics-off runs, and collection is opt-in per run.
  * **Host-side sinks**: ``obs.runlog`` (JSONL event writer),
    ``obs.compile_log`` (process-wide trace/retrace counters, the
    generalization of ``kernels/ops.trace_counts``), ``obs.profile``
    (named host spans on the profiler timeline), ``obs.bench``
    (schema-versioned BENCH_*.json artifacts), and ``obs.hlo_report``
    (trip-count-weighted collective/HBM hotspot reports from compiled
    HLO).

See docs/observability.md for the contracts.
"""
from . import bench, compile_log, metrics, profile, runlog
from .compile_log import TrackedCounts
from .metrics import MetricBag, metric_names, stage_metrics, step_metrics, \
    summarize
from .profile import annotate, named_scope, step
from .runlog import EVENT_SCHEMA_VERSION, RunLog, read_jsonl


def __getattr__(name: str):
    # hlo_report pulls in repro.launch's HLO parser; keep it lazy so the
    # kernels -> obs import (compile_log) stays featherweight and acyclic
    if name == "hlo_report":
        import importlib
        return importlib.import_module(".hlo_report", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "bench", "compile_log", "hlo_report", "metrics", "profile", "runlog",
    "TrackedCounts", "MetricBag", "metric_names", "stage_metrics",
    "step_metrics", "summarize", "annotate", "named_scope", "step",
    "RunLog", "read_jsonl", "EVENT_SCHEMA_VERSION",
]

"""Benchmark driver: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json PATH]

Prints a ``name,us_per_call,derived`` CSV line per benchmark at the end.
``--json PATH`` additionally writes a schema-versioned artifact (see
``repro.obs.bench`` for the envelope: ``schema_version``, ``env``,
``registry``, per-benchmark payloads with rows, per-point ``repro.opt``
registry specs, and backend axes) — the checked-in ``BENCH_*.json`` files
at the repo root are these artifacts, validated by
``python -m repro.obs.bench --validate`` and diffed by
``tools/bench_diff.py``.

Every per-benchmark payload uniformly carries ``backend`` (the
``repro.opt`` backend axis it exercised, defaulting to "reference") and
``specs`` (per-point registry specs where the benchmark has optimizer
points), so a result row is reproducible from the artifact alone via
``opt.from_spec``.

Benchmark modules are imported lazily (module name == benchmark name), so
``--only`` validation costs nothing and a typo'd name fails fast with the
list of valid names instead of silently printing an empty CSV. Setting
``REPRO_BENCH_FAST=1`` asks benchmarks that support it (kernel_roofline,
transport_zoo, fed_mesh) to run tiny CI-smoke shapes.
"""
import argparse
import importlib
import os
import sys
import time
import traceback

BENCH_NAMES = (
    "fig1_worker_comms",
    "fig_edge_scenarios",
    "fig2_linreg",
    "fig3_logreg",
    "table1_ijcnn",
    "table2_small",
    "table3_mnist",
    "fig10_stepsize",
    "fig11_epsilon",
    "fig12_descent",
    "transport_zoo",
    "serving",
    "roofline",
    "kernel_roofline",
    "fed_mesh",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write structured results (rows + per-benchmark "
                         "payloads) to PATH")
    args = ap.parse_args()

    if args.only is not None and args.only not in BENCH_NAMES:
        print(f"error: unknown benchmark {args.only!r}; valid names:",
              file=sys.stderr)
        for n in BENCH_NAMES:
            print(f"  {n}", file=sys.stderr)
        raise SystemExit(2)

    # every paper benchmark runs in f64 (see common.py); the old driver got
    # this from eagerly importing common — keep it explicit under lazy import
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    names = [args.only] if args.only else list(BENCH_NAMES)
    rows, payloads, failed = [], {}, []
    for name in names:
        t0 = time.time()
        try:
            fn = importlib.import_module(f"benchmarks.{name}").main
            out = fn()
            if isinstance(out, tuple):
                row, payload = out
            else:
                row, payload = out, {}
            dt = time.time() - t0
            rows.append(row)
            entry = {"row": row, "seconds": dt, **payload}
            # uniform artifact contract: every payload names its backend
            # axis and carries per-point specs (empty when the benchmark
            # has no optimizer points)
            entry.setdefault("backend", "reference")
            entry.setdefault("specs", [])
            payloads[name] = entry
            print(f"[{name}] done in {dt:.1f}s")
        except Exception:
            failed.append(name)
            traceback.print_exc()
    print("\nname,us_per_call,derived")
    for r in rows:
        print(r)
    if args.json:
        from repro import opt
        from repro.obs import bench
        stem = os.path.basename(args.json)
        if stem.startswith("BENCH_"):
            stem = stem[len("BENCH_"):]
        stem = stem[:-5] if stem.endswith(".json") else stem
        doc = bench.make_artifact(
            stem or "bench", payloads, failed=failed,
            registry=list(opt.names()))
        bench.write_artifact(doc, args.json)
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()

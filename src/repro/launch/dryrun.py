import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh): .lower().compile() the step
function against ShapeDtypeStruct inputs (no allocation), print/record
memory_analysis() + cost_analysis(), and parse the compiled HLO for
collective traffic (the §Roofline collective term).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out out.json
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..configs import ARCHS, ASSIGNED
from . import hlo_analysis
from .mesh import make_production_mesh
from .specs import INPUT_SHAPES, build_case

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_COLL_RE = re.compile(
    r"=\s*(?P<ty>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective traffic by op kind, from post-SPMD local shapes.

    Ring-traffic weights: all-reduce 2x result, all-gather 1x result,
    reduce-scatter ~1x operand (= k x result; approximated by the matching
    operand shape when present, else result), all-to-all / permute 1x.
    """
    out = {k: {"count": 0, "bytes": 0} for k in
           ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")}
    for m in _COLL_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue  # paired with -start
        op = m.group("op")
        b = _shape_bytes(m.group("ty"))
        w = 2 if op == "all-reduce" else 1
        out[op]["count"] += 1
        out[op]["bytes"] += w * b
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def run_case(arch: str, shape: str, multi_pod: bool, strategy: str = None,
             opts=(), **case_kw) -> dict:
    from ..models import tuning
    for o in opts:
        tuning.set_flags(**{o: True})
    cfg = ARCHS[arch]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if opts:
        tuning.set_mesh(mesh)
    if strategy is None:
        # The scan strategy also shards the pod axis (batch + stale-gradient
        # bank FSDP over ("pod","data")), so the multi-pod dry-run uses it;
        # the pod strategy is exercised on small meshes in
        # tests/test_distributed.py.
        strategy = "scan"
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "opts": list(opts),
           "strategy": strategy if INPUT_SHAPES[shape]["kind"] == "train"
           else "-"}
    t0 = time.time()
    try:
        case = build_case(cfg, shape, mesh, strategy=strategy, **case_kw)
        with mesh:
            jitted = jax.jit(case.fn, donate_argnums=case.donate)
            lowered = jitted.lower(*case.args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        ana = hlo_analysis.analyze(hlo)
        rec.update(
            ok=True, note=case.note,
            lower_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1),
            # loop-aware per-device totals (launch/hlo_analysis.py)
            flops=ana["flops"],
            hbm_bytes=ana["hbm_bytes"],
            collective_bytes=ana["collective_bytes"],
            collectives=ana["collectives"],
            # raw XLA numbers (loop bodies counted once) for reference
            xla_flops=cost.get("flops", 0.0),
            xla_bytes_accessed=cost.get("bytes accessed", 0.0),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
            ),
            hlo_bytes=len(hlo),
        )
        print(f"[OK] {arch} {shape} {rec['mesh']} "
              f"compile={rec['compile_s']}s flops/dev={rec['flops']:.3e} "
              f"hbm/dev={rec['hbm_bytes']:.3e}B "
              f"coll/dev={rec['collective_bytes']:.3e}B "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[FAIL] {arch} {shape} {rec['mesh']}: {rec['error']}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--strategy", default=None, choices=["scan", "pod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--quantize", default=None, choices=["int8"])
    ap.add_argument("--remat", default="full")
    ap.add_argument("--num-workers", type=int, default=None)
    ap.add_argument("--moe-mode", default=None, choices=["scan","grouped"])
    ap.add_argument("--opt", action="append", default=[],
                    help="enable a tuning flag (repeatable); see "
                         "repro/models/tuning.py")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]

    kw = {}
    if args.quantize:
        kw["quantize"] = args.quantize
    if args.moe_mode:
        kw["moe_mode"] = args.moe_mode
    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                skw = dict(kw)
                if INPUT_SHAPES[shape]["kind"] == "train":
                    skw["remat"] = args.remat
                    if args.num_workers:
                        skw["num_workers"] = args.num_workers
                records.append(run_case(arch, shape, mp,
                                        strategy=args.strategy,
                                        opts=tuple(args.opt), **skw))
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(records, f, indent=1)
    n_ok = sum(r["ok"] for r in records)
    print(f"\n{n_ok}/{len(records)} cases compiled successfully")
    if n_ok < len(records):
        raise SystemExit(1)


if __name__ == "__main__":
    main()

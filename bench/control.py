"""Readings that set the limits of ``correct``: the control and the faults.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it builds the cell's inputs at full size and puts, in the
program's place, (a) the reference computed one precision lower (bfloat16,
the control) and (b) the reference with a planted fault: half of the batch
(or of the writers) left out with the mean taken over the rest, and, on a
sharded cell, the fold between chips left out. Each is compared with the
float32 reference exactly as a run compares the program, and the numbers
are printed as one JSON line per seed and variant, with the device they ran
on. Nothing here runs the program, so it runs on whatever device JAX has;
the limits' readings are those taken on the chip. A state left unchanged
reads 1 on the change and needs no run. The benchmark's own runs never run
this; it exists so the limits can be re-derived.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def fed_variants(cell, seed: int, devices) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import harness
    from bench.drivers import fed as fed_driver

    cfg, tr = cell.config, cell.traffic
    task_mod = harness.config_part(cfg["name"], "task")
    ref_mod = harness.config_part(cfg["name"], "ref")
    data, _ = task_mod.make_population(cfg, seed, devices[:1])
    kw = dict(participation=tr["participation"], loss_prob=tr["loss_prob"],
              quorum=tr["quorum"], seed=seed)
    n = fed_driver.CHECK_ROUNDS

    def as_history(r):
        import types
        payload = r["payload_bytes"]
        return types.SimpleNamespace(
            objective=r["objective"], mask=r["mask"], attempted=r["attempted"],
            participated=r["participated"], delivered=r["delivered"],
            quorum_met=r["quorum_met"],
            bytes_cum=np.cumsum(r["attempted"]) * payload)

    variants = {"control_bf16": dict(data=data, dtype=jnp.bfloat16)}
    m = cfg["clients"]
    # half of the writers left out, the mean over the rest: the other half's
    # per-writer f_m and gradients count double
    half = dict(data)
    half["n"] = jnp.where(jnp.arange(m) % 2 == 0, data["n"], 0)
    variants["fault_half"] = dict(data=half, double=True)
    if tr["shards"] > 1:
        block = m // tr["shards"]
        own = dict(data)
        own["n"] = jnp.where(jnp.arange(m) < block, data["n"], 0)
        variants["fault_nofold"] = dict(data=own)

    out = []
    for name, v in variants.items():
        vcfg = dict(cfg)
        if v.get("double"):
            vcfg["train_images"] = cfg["train_images"] // 2
        got = ref_mod.run(vcfg, v["data"], rounds=n,
                          dtype=v.get("dtype", jnp.float32), **kw)
        ref = ref_mod.run(cfg, data, rounds=n, forced=got["mask"], **kw)
        checks = fed_driver.compare(as_history(got), ref, cfg["limits"])
        out.append({"variant": name, "seed": seed,
                    **{c.name: c.value for c in checks}})
        jax.clear_caches()
    return out


def train_variants(cell, seed: int) -> list:
    import jax.numpy as jnp

    from bench import harness
    from bench.drivers import train as train_driver

    cfg, tr = cell.config, cell.traffic
    ref_mod = harness.config_part(cfg["name"], "ref")
    truth = ref_mod.train(cfg, tr, seed, steps=train_driver.CHECK_STEPS)
    out = []
    for name, kw in (("control_bf16", dict(dtype=jnp.bfloat16)),
                     ("fault_half", dict(half=True))):
        got = ref_mod.train(cfg, tr, seed, steps=train_driver.CHECK_STEPS,
                            **kw)
        checks = train_driver.compare(got, truth, 0.0, cfg["limits"])
        out.append({"variant": name, "seed": seed,
                    **{c.name: c.value for c in checks}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness

    import jax

    cell = harness.find_cell(args.workload)
    harness.configure_jax()
    devices = jax.devices()[:1]
    for seed in args.seeds:
        rows = (train_variants(cell, seed) if cell.traffic["driver"] ==
                "train" else fed_variants(cell, seed, devices))
        for row in rows:
            print(json.dumps(dict(row, device=devices[0].device_kind)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

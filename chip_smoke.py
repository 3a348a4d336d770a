"""Bring-up smoke run: the CHB main path on a TPU, checked against the
reference backend.

    python chip_smoke.py               # one chip: chb_step, trainer, fed_mesh
    python chip_smoke.py --four-chips  # four chips: fed_mesh at K=4 vs K=1

Phases, one output line each (a name, then a JSON object of its numbers):

  chb_step[dense], chb_step[int8]
      ``opt.make("chb", ..., backend="pallas")`` (the Mosaic kernels)
      against ``backend="reference"``, M=4 workers over
      ``chb-paper-lm-124m`` at full width (d_model 768, d_ff 3072, vocab
      32768) with depth cut to 2 layers: the inputs and both backends'
      outputs of one int8 step must fit in one chip's 16 GB. Each step
      starts both backends from the same state and the same gradients
      (``model.train_loss`` on seeded ``lm_data`` batches). Masks, uplink
      counts and byte counters must be equal; floats within ``F32_RTOL``
      of each leaf's magnitude (plus, for int8, one quantization step,
      since backends may round a quotient that lands on a .5 tie
      differently).
  trainer
      ``repro.train.trainer.train`` on the full 12-layer model, 5 steps.
  fed_mesh
      ``fed.run_mesh`` over 10^5 clients x d=16 on a 1-shard mesh,
      pallas against reference.

With ``--four-chips`` only the client-sharded path runs: ``fed.run_mesh``
(pallas) at K=4 shards against K=1 on the same host, which must draw the
same masks, counts and quorum decisions (anchor (b) of
docs/fed_scaling.md).

The last line is ``{"ok": true, "device": {...}}``. Any failed check, a
first device that is not a TPU, or kernels that would run in the Pallas
interpreter exit non-zero before that line. Wall times printed are smoke
timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "chb-paper-lm-124m"
M = 4
ALPHA, BETA, EPS1_SCALE = 3e-2, 0.4, 4.0
# at the random init the chb_step phase's eq.-(8) ratio
# ||delta||^2 / (eps1 ||step||^2) is ~2.0 at step 1 and ~1.7 at step 2 for
# EPS1_SCALE; this scale puts them at ~1.15 and ~0.99, so its steps both
# transmit and censor
STEP_EPS1_SCALE = 7.0
GLOBAL_BATCH, SEQ_LEN = 32, 256
CLIENTS, ROUNDS = 100_000, 5
# float agreement between backends (and shard counts), relative to the
# largest magnitude in the leaf: ~80 f32 ulps, room for the reduction
# orders of tiled partial sums and the K-way fold
F32_RTOL = 1e-5
# the fed_mesh scenario: partial participation, lossy uplinks and a
# quorum, so draws, channel gating and quorum decisions are all exercised
SCENARIO = dict(participation=0.5, loss_prob=0.3, quorum=0.5, seed=3)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report(phase: str, numbers: dict) -> None:
    print(f"{phase} {json.dumps(numbers)}", flush=True)


def _leaf_errors(out, ref, slack=None) -> list[float]:
    """Per leaf, max |out - ref| over its tolerance (<= 1 passes)."""
    import jax
    import jax.numpy as jnp

    def one(a, b, s):
        tol = F32_RTOL * jnp.max(jnp.abs(b)) + s
        return jnp.max(jnp.abs(a - b)) / jnp.maximum(tol, 1e-30)

    leaves_o = jax.tree_util.tree_leaves(out)
    leaves_r = jax.tree_util.tree_leaves(ref)
    slacks = [0.0] * len(leaves_r) if slack is None \
        else jax.tree_util.tree_leaves(slack)
    return [float(one(a, b, s)) for a, b, s in zip(leaves_o, leaves_r, slacks)]


def chb_step_phase(cfg, quantize, steps: int = 3, seed: int = 0) -> dict:
    """Pallas against reference ``ComposedOptimizer.step``, per step."""
    import jax
    import jax.numpy as jnp

    from repro import opt
    from repro.data import lm_data
    from repro.kernels import ops as kernel_ops
    from repro.models import model

    pal = opt.make("chb", ALPHA, M, beta=BETA, eps1_scale=STEP_EPS1_SCALE,
                   quantize=quantize, backend="pallas")
    ref = dataclasses.replace(pal, backend="reference")

    def loss(params, batch):
        return model.train_loss(params, cfg, batch, remat="none")[0]

    # one worker's gradient at a time, as the trainer's scan does
    grad_fn = jax.jit(lambda p, b: jax.lax.map(
        lambda wb: jax.grad(loss)(p, wb), b))
    ref_step = jax.jit(ref.step)
    # pallas runs second and takes over the state, so it may reuse the
    # state's buffers: the inputs and ONE step's outputs stay resident
    pal_jit = jax.jit(pal.step, donate_argnums=(0, 1))
    int8_scales = jax.jit(lambda g, h, e: kernel_ops.tree_int8_stats(
        g, h, e)[1])

    params = model.init_params(jax.random.PRNGKey(seed), cfg)
    state = jax.jit(pal.init)(params)
    data = lm_data.batch_iterator(cfg, global_batch=GLOBAL_BATCH,
                                  seq_len=SEQ_LEN, num_workers=M, seed=seed)
    out = {"params": int(sum(x.size for x in jax.tree_util.tree_leaves(
        params))), "masks": [], "pallas_s": [], "reference_s": []}
    worst = {"params": 0.0, "ghat": 0.0, "err": 0.0}
    dsq_rel = 0.0
    for k in range(steps):
        batch = jax.tree_util.tree_map(jnp.asarray, next(data))
        grads = grad_fn(params, batch)
        if k == 0:
            compiled = pal_jit.lower(state, params, grads).compile()
            check("tpu_custom_call" in compiled.as_text(),
                  f"chb_step[{quantize or 'dense'}]: no Mosaic kernel in "
                  "the compiled pallas step")
            ma = compiled.memory_analysis()
            out["compiled_gb"] = {
                "arguments": ma.argument_size_in_bytes / 1e9,
                "outputs": ma.output_size_in_bytes / 1e9,
                "temporaries": ma.temp_size_in_bytes / 1e9}
        # slack for int8: one quantization step per worker and leaf (the
        # scales derive from the pre-step state, so take them first)
        scales = int8_scales(grads, state.ghat, state.err) if quantize \
            else None

        t0 = time.perf_counter()
        r_state, r_params, r_stats = jax.block_until_ready(
            ref_step(state, params, grads))
        t1 = time.perf_counter()
        state, params, p_stats = jax.block_until_ready(
            compiled(state, params, grads))
        t2 = time.perf_counter()
        out["reference_s"].append(t1 - t0)
        out["pallas_s"].append(t2 - t1)
        del grads

        p_mask, r_mask = np.asarray(p_stats.mask), np.asarray(r_stats.mask)
        out["masks"].append(p_mask.astype(int).tolist())
        if not np.array_equal(p_mask, r_mask):
            dsq = np.asarray(r_stats.delta_sq, np.float64)
            thr = pal.eps1 * float(r_stats.step_sq)
            margin = {int(w): float((dsq[w] - thr) / max(thr, 1e-30))
                      for w in np.nonzero(p_mask != r_mask)[0]}
            check(False, f"chb_step[{quantize or 'dense'}] step {k}: masks "
                  f"{p_mask} vs {r_mask}, eq.-(8) margin {margin}")
        for f in state.comm._fields:
            check(np.array_equal(np.asarray(getattr(state.comm, f)),
                                 np.asarray(getattr(r_state.comm, f))),
                  f"chb_step step {k}: comm.{f} differs")
        dsq_rel = max(dsq_rel, float(np.max(
            np.abs(np.asarray(p_stats.delta_sq) - np.asarray(r_stats.delta_sq))
            / np.asarray(r_stats.delta_sq))))
        if quantize:
            bank_slack = jax.tree_util.tree_map(jnp.max, scales)
            param_slack = jax.tree_util.tree_map(
                lambda s: ALPHA * jnp.sum(s), scales)
        else:
            bank_slack = param_slack = None
        worst["params"] = max(worst["params"], *_leaf_errors(
            params, r_params, param_slack))
        worst["ghat"] = max(worst["ghat"], *_leaf_errors(
            state.ghat, r_state.ghat, bank_slack))
        if quantize:
            worst["err"] = max(worst["err"], *_leaf_errors(
                state.err, r_state.err, bank_slack))
        del r_state, r_params, r_stats, scales

    out["uplinks"] = np.asarray(state.comm.uplink_count).tolist()
    out["uplink_bytes"] = int(state.comm.uplink_bytes)
    out["worst_over_tol"] = worst
    out["delta_sq_maxrel"] = dsq_rel
    stats = jax.devices()[0].memory_stats() or {}
    out["device_peak_gb"] = stats.get("peak_bytes_in_use", 0) / 1e9
    check(all(v <= 1.0 for v in worst.values()),
          f"chb_step[{quantize or 'dense'}]: floats beyond tolerance {worst}")
    return out


def trainer_phase(cfg, steps: int = 5) -> dict:
    """The trainer's normal path (dense transport, scan strategy)."""
    from repro.train.trainer import TrainConfig, train

    tc = TrainConfig(algorithm="chb", num_workers=M,
                     global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN,
                     eps1_scale=EPS1_SCALE, steps=steps, log_every=1)
    _, state, history = train(cfg, tc, verbose=False)
    losses = [h["loss"] for h in history]
    uplinks = int(state.comm.total_uplinks)
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"trainer: losses {losses}")
    check(uplinks <= steps * M, f"trainer: {uplinks} uplinks > {steps * M}")
    wall = [h["wall_s"] for h in history]
    return {"losses": losses, "uplinks": uplinks,
            "transmitted": [h["transmitted"] for h in history],
            "step_wall_s": [round(b - a, 1)
                            for a, b in zip([0.0] + wall, wall)]}


def _run_mesh(backend: str, shards: int, clients: int, rounds: int):
    from repro import fed, opt
    from repro.data import edge_tasks
    from repro.launch.mesh import make_client_mesh

    task = edge_tasks.make_edge_quadratics(clients, d=16, seed=0)
    o = opt.make("chb", 0.5 / clients, clients, eps1=4.0, backend=backend)
    t0 = time.perf_counter()
    mh = fed.run_mesh(o, task, rounds, mesh=make_client_mesh(shards),
                      scenario=fed.MeshScenario(**SCENARIO), bake_data=False)
    return mh, time.perf_counter() - t0


def _compare_mesh(what: str, got, want) -> dict:
    """Draws and counts equal; floats within the F32_RTOL bound."""
    import jax

    for f in ("mask", "participated", "attempted", "delivered",
              "quorum_met", "bytes_cum"):
        diff = getattr(got, f) != getattr(want, f)
        check(not diff.any(), f"{what}: {int(diff.sum())} entries of {f} "
              f"differ, in rounds {sorted(set(np.nonzero(diff)[0]))}")
    obj = float(np.max(np.abs(got.objective - want.objective)
                       / np.abs(want.objective)))
    # on the host: the two runs' params live on different device sets
    host = lambda t: [np.asarray(x) for x in t]  # noqa: E731
    params = max(_leaf_errors(host(jax.tree_util.tree_leaves(
        got.final_params)), host(jax.tree_util.tree_leaves(
            want.final_params))))
    check(obj <= F32_RTOL, f"{what}: objective maxrel {obj}")
    check(params <= 1.0, f"{what}: params beyond tolerance ({params})")
    return {"objective_maxrel": obj, "params_over_tol": params}


def fed_mesh_phase(clients: int = CLIENTS, rounds: int = ROUNDS) -> dict:
    """``fed.run_mesh`` on one shard, pallas against reference."""
    pal, pal_s = _run_mesh("pallas", 1, clients, rounds)
    ref, ref_s = _run_mesh("reference", 1, clients, rounds)
    out = _compare_mesh("fed_mesh pallas vs reference", pal, ref)
    out.update(clients=clients, rounds=rounds,
               attempted=pal.attempted.tolist(),
               quorum_met=pal.quorum_met.tolist(),
               uplink_bytes=int(pal.bytes_cum[-1]),
               objective=pal.objective.tolist(),
               pallas_s=pal_s, reference_s=ref_s)
    return out


def fed_mesh_shards_phase(clients: int = CLIENTS, rounds: int = ROUNDS,
                          shards: int = 4) -> dict:
    """``fed.run_mesh`` (pallas) over ``shards`` devices against one."""
    base, base_s = _run_mesh("pallas", 1, clients, rounds)
    wide, wide_s = _run_mesh("pallas", shards, clients, rounds)
    out = _compare_mesh(f"fed_mesh K={shards} vs K=1", wide, base)
    out.update(clients=clients, rounds=rounds, shards=shards,
               attempted=wide.attempted.tolist(),
               quorum_met=wide.quorum_met.tolist(),
               uplink_bytes=int(wide.bytes_cum[-1]),
               k1_s=base_s, wide_s=wide_s)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fed.run_mesh at K=4 against K=1")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's first device is "
                         f"{devices[0].platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU chips, found "
                         f"{len(devices)}")
    if jax.config.jax_enable_x64:
        raise SystemExit("chip_smoke: runs in f32; unset JAX_ENABLE_X64")

    from repro.configs import get
    from repro.kernels.common import interpret_default
    from repro.launch.compile_cache import enable_compile_cache

    if interpret_default():
        raise SystemExit("chip_smoke: kernels would run in the Pallas "
                         "interpreter, not Mosaic")
    enable_compile_cache()

    if args.four_chips:
        report("fed_mesh[K=4]", fed_mesh_shards_phase())
    else:
        cfg = get(ARCH)
        short = dataclasses.replace(cfg, num_layers=2).validate()
        for quantize in (None, "int8"):
            report(f"chb_step[{quantize or 'dense'}]",
                   chb_step_phase(short, quantize))
            gc.collect()
        report("trainer", trainer_phase(cfg))
        gc.collect()
        report("fed_mesh", fed_mesh_phase())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

"""Drive ``repro.fed.run_mesh`` over a configuration's client population.

Set-up makes the population on the device from the seed and makes three
calls that the window does not time: a two-round call (which compiles on
a cold cache), then a short and a longer call whose difference gives the
warm round time and the fixed cost of a call. The window is one call of R
rounds, R sized from those to last about ``--seconds``.

``correct`` compares the window's own first rounds with the plain
reference (``bench/configs/<config>.ref.py``): the objective by its
relative gap, the program's uplink decisions by how
far the reference puts them from the eq.-(8) threshold where the two
differ, and every count, quorum decision and byte total exactly.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness

CHECK_ROUNDS = 4        # objectives at theta^0..theta^3: three updates
SHORT, LONG = 2, 42     # rounds of the two warm calls that time a round


def _run_mesh(o, task, mesh, scenario):
    from repro import fed

    def call(rounds: int):
        return fed.run_mesh(o, task, rounds, mesh=mesh, scenario=scenario,
                            bake_data=False, donate=True)
    return call


def participants_work(cfg: dict, tr: dict, seed: int, rounds: int,
                      counts: np.ndarray) -> dict:
    """FLOPs and bytes the window's rounds require of their participants,
    by the configuration's own count (``<config>.work.py``)."""
    if tr["participation"] >= 1.0:
        joined = np.tile(counts, rounds)
    else:
        import jax
        import jax.numpy as jnp
        ref = harness.config_part(cfg["name"], "ref")
        ids = jnp.arange(cfg["clients"], dtype=jnp.uint32)
        draw = jax.jit(lambda k: ref._draws(seed, k, ids)[0])
        joined = np.concatenate([
            counts[np.asarray(draw(k)) < tr["participation"]]
            for k in range(rounds)])
    return harness.config_part(cfg["name"], "work").round_work(cfg, joined)


def _rel_gap(got, want) -> float:
    """Largest |got - want| / |want|; a zero beside a zero reads 0."""
    want = np.asarray(want, np.float64)
    diff = np.abs(np.asarray(got, np.float64) - want)
    return float(np.max(np.where(diff == 0, 0.0,
                                 diff / np.maximum(np.abs(want), 1e-300))))


def compare(hist, ref: dict, limits: dict) -> list:
    n = len(ref["objective"])
    obj = np.asarray(hist.objective[:n], np.float64)
    obj_gap = _rel_gap(obj, ref["objective"])
    mask = np.asarray(hist.mask[:n]).astype(np.int64)
    att = np.asarray(hist.attempted[:n])
    bytes_want = np.cumsum(ref["attempted"]) * ref["payload_bytes"]
    mismatch = int(
        np.sum(att != mask.sum(axis=1))
        + np.sum(att != ref["attempted"])
        + np.sum(np.asarray(hist.participated[:n]) != ref["participated"])
        + np.sum(np.asarray(hist.delivered[:n]) != ref["delivered"])
        + np.sum(np.asarray(hist.quorum_met[:n]) != ref["quorum_met"])
        + np.sum(np.asarray(hist.bytes_cum[:n]) != bytes_want))
    return [harness.Check("objective_gap", obj_gap, limits["objective_gap"]),
            harness.Check("flip_margin", float(ref["flip_margin"]),
                          limits["flip_margin"]),
            harness.Check("count_mismatch", float(mismatch), 0.0)]


def build(ctx: harness.Context):
    """The population, the task, the optimizer, the mesh and the scenario."""
    import jax

    from repro import fed, opt
    from repro.launch.mesh import make_client_mesh

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    mesh = make_client_mesh(tr["shards"])
    task_mod = harness.config_part(cfg["name"], "task")
    data, counts = task_mod.make_population(cfg, ctx.seed,
                                            list(mesh.devices.flat))
    jax.block_until_ready(data)
    task = task_mod.make_task(cfg, data)
    o = opt.make("chb", cfg["alpha"], cfg["clients"], beta=cfg["beta"],
                 eps1_scale=cfg["eps1_scale"], backend="pallas")
    scenario = fed.MeshScenario(participation=tr["participation"],
                                loss_prob=tr["loss_prob"],
                                quorum=tr["quorum"], seed=ctx.seed)
    return task, counts, o, mesh, scenario


def run(ctx: harness.Context) -> harness.Outcome:
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    t0 = time.perf_counter()
    task, counts, o, mesh, scenario = build(ctx)
    ctx.log("population", seconds=time.perf_counter() - t0)
    call = _run_mesh(o, task, mesh, scenario)

    warm = (SHORT, SHORT, LONG)     # a cold call, then two that time a round
    secs = []
    for i in range(len(warm) + 1):
        window = i == len(warm)
        if window:
            round_s = (secs[2] - secs[1]) / (LONG - SHORT)
            fixed_s = secs[1] - SHORT * round_s
            rounds = max(CHECK_ROUNDS, int(round(
                (ctx.seconds - max(fixed_s, 0.0)) / round_s)))
            gc.collect()
            setup_s = time.perf_counter() - ctx.t_start
            ctx.log("warm", first_call_s=secs[0], round_s=round_s,
                    call_fixed_s=fixed_s, rounds=rounds, setup_s=setup_s)
        # every call from this one line: see harness.entry_call
        hist, s = harness.entry_call(ctx, call, rounds if window else warm[i],
                                     window=window)
        secs.append(s)
        if not window:
            del hist
    window_s = secs[-1]
    peak = harness.memory_peak(ctx.devices)
    ctx.log("window", seconds=window_s, rounds=rounds,
            call_fixed_s=window_s - rounds * round_s)

    failed = int(np.sum(~np.isfinite(hist.objective)))
    hist = hist._replace(final_params=None)
    del o, call
    gc.collect()

    import jax

    t = time.perf_counter()
    ref_mod = harness.config_part(cfg["name"], "ref")
    # the reference runs on one chip, whichever way the program split it
    data = jax.device_put(task.worker_data, ctx.devices[0])
    del task
    ref = ref_mod.run(cfg, data, rounds=CHECK_ROUNDS,
                      participation=tr["participation"],
                      loss_prob=tr["loss_prob"], quorum=tr["quorum"],
                      seed=ctx.seed, forced=hist.mask[:CHECK_ROUNDS])
    ctx.log("reference", seconds=time.perf_counter() - t)
    checks = compare(hist, ref, cfg["limits"])

    extra = {}
    if ctx.trace:
        extra = participants_work(cfg, tr, ctx.seed, rounds, counts)
    extra.update(rounds=rounds, chips=len(ctx.devices))
    return harness.Outcome(
        setup_s=setup_s, window_s=window_s, attempted=rounds, failed=failed,
        end_to_end={"fed_rounds_per_s": rounds / window_s},
        checks=checks, work=extra, memory_peak_bytes=peak)

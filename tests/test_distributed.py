"""Distributed CHB strategies, run in subprocesses with 8 fake devices
(so the main pytest process keeps its single-device view)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import exactness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return json.loads(r.stdout.splitlines()[-1])


COMMON = textwrap.dedent("""
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get
    from repro.core import chb, distributed
    from repro.core.chb import FedOptConfig
    from repro.launch import sharding as shr
    from repro.launch import mesh as mk
    from repro.models import model
    from repro.data import lm_data

    cfg = get("chb-paper-lm-124m").reduced()
    fcfg = FedOptConfig(alpha=0.02, beta=0.4, eps1=2.0, num_workers=2)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    def loss_fn(p, b):
        return model.train_loss(p, cfg, b, remat="none")[0]
    lm = lm_data.MarkovLM(cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)
    raw = [lm.sample(rng, 8, 32) for _ in range(4)]
    batches = [{"tokens": jnp.asarray(r[:, :-1]),
                "labels": jnp.asarray(r[:, 1:])} for r in raw]
""")


def test_scan_strategy_matches_single_device_reference():
    """jit-sharded scan strategy on an 8-device mesh must equal the
    unsharded single-device run bit-for-bit in structure and closely in
    value."""
    code = COMMON + textwrap.dedent("""
        # reference: no mesh
        ref_state = distributed.init_scan_state(fcfg, params)
        ref_step = jax.jit(distributed.make_scan_step(fcfg, loss_fn))
        rp, rs = params, ref_state
        ref_losses, ref_tx = [], []
        for b in batches:
            wb = {k: v.reshape(2, 4, -1) for k, v in b.items()}
            rp, rs, m = ref_step(rp, rs, wb)
            ref_losses.append(float(m["loss"])); ref_tx.append(float(m["transmitted"]))

        # sharded: (4,2) mesh
        mesh = mk.make_auto_mesh((4,2), ("data","model"))
        sh = shr.params_shardings(jax.eval_shape(lambda: params), mesh)
        p2 = jax.tree_util.tree_map(jax.device_put, params, sh)
        st2 = distributed.init_scan_state(fcfg, p2)
        step2 = jax.jit(distributed.make_scan_step(fcfg, loss_fn))
        losses, txs = [], []
        with mesh:
            for b in batches:
                wb = {k: jax.device_put(v.reshape(2, 4, -1),
                                        NamedSharding(mesh, P(None, "data")))
                      for k, v in b.items()}
                p2, st2, m = step2(p2, st2, wb)
                losses.append(float(m["loss"])); txs.append(float(m["transmitted"]))
        print(json.dumps({"ref_losses": ref_losses, "losses": losses,
                          "ref_tx": ref_tx, "tx": txs}))
    """)
    out = run_sub(code)
    exactness.assert_close(out["losses"], out["ref_losses"],
                           "sharded_trainer")
    assert out["tx"] == out["ref_tx"]


def test_pod_strategy_matches_scan_strategy():
    """Pod strategy (shard_map manual over pod, workers=pods) must agree
    with the scan strategy (workers=batch groups) given identical data
    splits, on a (2,2,2) mesh."""
    import jax as _jax
    if not hasattr(_jax, "shard_map"):
        pytest.skip("partial-manual shard_map (auto=...) trips an XLA "
                    "SPMD-partitioner CHECK on jax 0.4.x; pod strategy "
                    "needs the top-level jax.shard_map API")
    code = COMMON + textwrap.dedent("""
        mesh = mk.make_auto_mesh((2,2,2), ("pod","data","model"))
        shp = shr.params_shardings(jax.eval_shape(lambda: params), mesh,
                                   fsdp_axes=("data",), gather_safe=True)
        # scan strategy reference (workers = 2 groups, same split as pods)
        p1 = jax.tree_util.tree_map(jax.device_put, params, shp)
        st1 = distributed.init_scan_state(fcfg, p1)
        step1 = jax.jit(distributed.make_scan_step(fcfg, loss_fn))
        # pod strategy
        p2 = jax.tree_util.tree_map(jax.device_put, params, shp)
        st2 = distributed.init_pod_state(fcfg, p2, mesh)
        step2 = jax.jit(distributed.make_pod_step(fcfg, loss_fn, mesh))
        l1s, l2s, t1s, t2s = [], [], [], []
        with mesh:
            for b in batches:
                wb = {k: v.reshape(2, 4, -1) for k, v in b.items()}
                p1, st1, m1 = step1(p1, st1, wb)
                fb = {k: jax.device_put(v, NamedSharding(mesh, P(("pod","data"))))
                      for k, v in b.items()}
                p2, st2, m2 = step2(p2, st2, fb)
                l1s.append(float(m1["loss"])); l2s.append(float(m2["loss"]))
                t1s.append(float(m1["transmitted"])); t2s.append(float(m2["transmitted"]))
        d = max(abs(a-b) for a, b in zip(l1s, l2s))
        print(json.dumps({"l1": l1s, "l2": l2s, "t1": t1s, "t2": t2s,
                          "maxdiff": d}))
    """)
    out = run_sub(code)
    assert out["maxdiff"] < 3e-3, out
    assert out["t1"] == out["t2"]


def test_quantized_scan_strategy_runs():
    code = COMMON + textwrap.dedent("""
        import dataclasses
        fq = dataclasses.replace(fcfg, quantize="int8")
        st = distributed.init_scan_state(fq, params)
        step = jax.jit(distributed.make_scan_step(fq, loss_fn))
        p = params
        losses = []
        for b in batches:
            wb = {k: v.reshape(2, 4, -1) for k, v in b.items()}
            p, st, m = step(p, st, wb)
            losses.append(float(m["loss"]))
        ok = all(np.isfinite(losses))
        print(json.dumps({"ok": bool(ok), "losses": losses,
            "bytes": float(st.comm.uplink_bytes)}))
    """)
    out = run_sub(code)
    assert out["ok"] and out["bytes"] > 0


# ===================================================== fed mesh runtime
FED_COMMON = textwrap.dedent("""
    import jax, json
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    import repro.opt as ropt
    from repro.core import simulator
    from repro.data import paper_tasks
    from repro.fed.mesh import run_mesh, MeshScenario
    from repro.launch.mesh import make_client_mesh

    bundle = paper_tasks.make_linear_regression(m=8, n_per=20, d=12, seed=1)
    task = bundle.task
    opt = ropt.make("chb", bundle.alpha_paper, num_workers=8)
""")


def test_fed_mesh_shard_count_invariance():
    """Anchor (b): K in {1, 2, 8} draws the same masks for every client
    (bit-equal), same counts/quorum decisions, and float trajectories
    within the K-way fold's reduction-order ulps."""
    code = FED_COMMON + textwrap.dedent("""
        sc = MeshScenario(participation=0.7, loss_prob=0.2, quorum=0.5,
                          seed=3)
        runs = {K: run_mesh(opt, task, 10, mesh=make_client_mesh(K),
                            scenario=sc) for K in (1, 2, 8)}
        base = runs[1]
        out = {}
        for K in (2, 8):
            mh = runs[K]
            p1 = np.concatenate([np.ravel(x) for x in
                                 jax.tree_util.tree_leaves(base.final_params)])
            pk = np.concatenate([np.ravel(x) for x in
                                 jax.tree_util.tree_leaves(mh.final_params)])
            out[str(K)] = {
                "masks_bitwise": bool(np.array_equal(base.mask, mh.mask)),
                "counts_eq": bool(
                    np.array_equal(base.participated, mh.participated)
                    and np.array_equal(base.attempted, mh.attempted)
                    and np.array_equal(base.delivered, mh.delivered)),
                "met_eq": bool(np.array_equal(base.quorum_met,
                                              mh.quorum_met)),
                "obj_maxrel": float(np.max(np.abs(
                    base.objective - mh.objective)
                    / np.abs(base.objective))),
                "params_maxdiff": float(np.max(np.abs(p1 - pk))),
            }
        print(json.dumps(out))
    """)
    out = run_sub(code)
    for k, rec in out.items():
        assert rec["masks_bitwise"], (k, rec)
        assert rec["counts_eq"] and rec["met_eq"], (k, rec)
        assert rec["obj_maxrel"] < 1e-12, (k, rec)
        assert rec["params_maxdiff"] < 1e-12, (k, rec)


def test_fed_mesh_sync_anchor_on_eight_shards():
    """Anchor (a) survives sharding: the ideal scenario over 8 shards
    keeps censor masks bit-equal to the single-program simulator, with
    objective/params drift bounded by the 8-way fold reorder."""
    code = FED_COMMON + textwrap.dedent("""
        hist = simulator.run(opt, task, 10)
        mh = run_mesh(opt, task, 10, mesh=make_client_mesh(8))
        print(json.dumps({
            "masks_bitwise": bool(np.array_equal(
                np.asarray(hist.mask).astype(np.int8), mh.mask)),
            "comm_eq": bool(np.array_equal(np.asarray(hist.comm_cum),
                                           mh.comm_cum)),
            "obj_maxrel": float(np.max(np.abs(
                np.asarray(hist.objective) - mh.objective)
                / np.abs(np.asarray(hist.objective)))),
        }))
    """)
    out = run_sub(code)
    assert out["masks_bitwise"] and out["comm_eq"], out
    assert out["obj_maxrel"] < 1e-13, out


def test_fed_mesh_donation_safe_across_shards():
    """donate=True at K=2 is bit-identical to donate=False — including
    the prev_params re-injection after the server's quorum select."""
    code = FED_COMMON + textwrap.dedent("""
        sc = MeshScenario(participation=0.8, loss_prob=0.3, quorum=0.6,
                          seed=5)
        mesh = make_client_mesh(2)
        a = run_mesh(opt, task, 12, mesh=mesh, scenario=sc)
        b = run_mesh(opt, task, 12, mesh=mesh, scenario=sc, donate=True)
        print(json.dumps({
            "obj_eq": bool(np.array_equal(a.objective, b.objective)),
            "mask_eq": bool(np.array_equal(a.mask, b.mask)),
            "met_eq": bool(np.array_equal(a.quorum_met, b.quorum_met)),
        }))
    """)
    out = run_sub(code)
    assert all(out.values()), out


def test_fed_mesh_indivisible_clients_raise():
    """M must divide the shard count — loud ValueError, not a silent
    ragged split."""
    code = FED_COMMON + textwrap.dedent("""
        try:
            run_mesh(opt, task, 2, mesh=make_client_mesh(3))
            print(json.dumps({"raised": False, "msg": ""}))
        except ValueError as e:
            print(json.dumps({"raised": True, "msg": str(e)[:120]}))
    """)
    out = run_sub(code)
    assert out["raised"] and "divis" in out["msg"], out


def test_fed_sweep_mesh_partition_is_bitwise():
    """Scenario-grid partitioning over the mesh is a pure partition:
    results are bit-identical to the unpartitioned sweep at K in
    {1, 2, 8}."""
    code = FED_COMMON + textwrap.dedent("""
        from repro.sweep.fed_sweep import run_fed_sweep, FedScenarioGrid
        grid = FedScenarioGrid(loss_prob=(0.0, 0.2),
                               participation=(1.0, 0.6),
                               quorum=(1.0, 0.5), seed=(0,))
        base = run_fed_sweep(opt, task, grid, 6)
        out = {}
        for K in (1, 2, 8):
            r = run_fed_sweep(opt, task, grid, 6, mesh=make_client_mesh(K))
            out[str(K)] = bool(
                np.array_equal(base.objective, r.objective)
                and np.array_equal(base.transmit_mask, r.transmit_mask)
                and np.array_equal(base.delivered_mask, r.delivered_mask)
                and np.array_equal(base.energy_cum, r.energy_cum))
        print(json.dumps(out))
    """)
    out = run_sub(code)
    assert all(out.values()), out


def test_hlo_report_ranks_client_fold_collective():
    """The quorum fold is the mesh runtime's ONE cross-shard collective;
    obs.hlo_report must surface its all-reduce as the top collective row."""
    code = textwrap.dedent("""
        import jax, json
        import jax.numpy as jnp
        from repro.core.distributed import make_client_fold
        from repro.launch.mesh import make_client_mesh
        from repro.launch.sharding import stack_shards
        from repro.obs import hlo_report

        mesh = make_client_mesh(8)
        fold = make_client_fold(mesh)
        pieces = [jax.device_put(jnp.ones((1, 64), jnp.float32), d)
                  for d in mesh.devices.flat]
        stacked = stack_shards([{"g": p} for p in pieces], mesh)
        text = hlo_report.compiled_text(jax.jit(fold), stacked)
        rep = hlo_report.report(text, top=5)
        kinds = [r["kind"] for r in rep["collectives"]]
        print(json.dumps({"kinds": kinds,
                          "total": rep["totals"]["collectives"]}))
    """)
    out = run_sub(code)
    assert "all-reduce" in out["kinds"], out

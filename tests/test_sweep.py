"""repro.sweep: grid enumeration, sweep-vs-loop bit-exactness for the whole
algorithm family (incl. int8 and multi-seed grids), the fed scenario sweep's
sync anchor, export round-trips, and the benchmark driver's --only guard."""
import json
import os
import subprocess
import sys

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import sweep
from repro.core import baselines, chb, simulator
from repro.core.censoring import paper_eps1
from repro.data import paper_tasks


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=5, n_per=30, d=20, seed=0)


def _task_factory(seed, m):
    return paper_tasks.make_linear_regression(
        m=m, n_per=30, d=20, seed=seed).task


# ------------------------------------------------------------------- grid
def test_grid_cartesian_product():
    g = sweep.ConfigGrid(alpha=(0.1, 0.2), beta=(0.0, 0.4),
                         eps1=(0.0, 1.0), seed=(0, 1))
    pts = g.points()
    assert len(pts) == g.num_points == 16
    # row-major in declared field order: alpha slowest, seed fastest here
    assert pts[0] == sweep.GridPoint(0.1, 0.0, 0.0, 0, None, None)
    assert pts[1].seed == 1 and pts[1].alpha == 0.1
    assert pts[-1] == sweep.GridPoint(0.2, 0.4, 1.0, 1, None, None)
    assert pts[0].algo_name == "gd" and pts[-1].algo_name == "chb"


def test_grid_eps1_scale_resolution():
    g = sweep.ConfigGrid(alpha=(0.1,), eps1_scale=(0.5,))
    (p,) = g.points(default_num_workers=4)
    assert p.eps1 == pytest.approx(paper_eps1(0.1, 4, 0.5))
    with pytest.raises(ValueError):
        g.points()      # no M anywhere -> cannot resolve the scale
    with pytest.raises(ValueError):
        sweep.ConfigGrid(alpha=(0.1,), eps1=(1.0,), eps1_scale=(0.5,))
    with pytest.raises(ValueError):
        sweep.ConfigGrid(alpha=(0.1,), quantize=("int4",))


# ------------------------------------------- sweep-vs-loop bit-exactness
def test_sweep_matches_per_point_run_exactly(linreg):
    """A >=8-point batched sweep covering GD/HB/LAG/CHB at two step sizes
    must reproduce each per-point simulator.run trajectory bit-exactly."""
    a = linreg.alpha_paper
    points = []
    for s in (1.0, 0.5):
        for algo in ("gd", "hb", "lag", "chb"):
            cfg = baselines.ALGORITHMS[algo](a * s, 5)
            points.append(sweep.GridPoint(alpha=cfg.alpha, beta=cfg.beta,
                                          eps1=cfg.eps1))
    assert len(points) >= 8
    res = sweep.run_sweep(points, task=linreg.task, num_iters=120)
    assert res.num_programs == 1        # one compiled program for all eight
    for p, hist in zip(points, res.histories):
        cfg = chb.FedOptConfig(alpha=p.alpha, beta=p.beta, eps1=p.eps1,
                               num_workers=5)
        exactness.assert_bitwise(hist, simulator.run(cfg, linreg.task, 120))


def test_sweep_int8_quantized_path_exact(linreg):
    """Mixed dense/int8 grids partition into two programs; the quantized
    error-feedback path must stay bit-exact too."""
    a = linreg.alpha_paper
    eps = paper_eps1(a, 5)
    points = [
        sweep.GridPoint(alpha=a, beta=0.4, eps1=eps),
        sweep.GridPoint(alpha=a, beta=0.4, eps1=eps, quantize="int8"),
        sweep.GridPoint(alpha=a, beta=0.0, eps1=0.0, quantize="int8"),
    ]
    res = sweep.run_sweep(points, task=linreg.task, num_iters=100)
    assert res.num_programs == 2
    for p, hist in zip(points, res.histories):
        cfg = chb.FedOptConfig(alpha=p.alpha, beta=p.beta, eps1=p.eps1,
                               num_workers=5, quantize=p.quantize)
        exactness.assert_bitwise(hist, simulator.run(cfg, linreg.task, 100))
    # quantized transmissions ship ~8x fewer bytes (f64 -> int8 + scale)
    assert res.uplink_bytes[1] < 0.25 * res.uplink_bytes[0]


def test_sweep_seed_axis_exact():
    """Seed (task) axes partition per seed and stay bit-exact per point."""
    b = paper_tasks.make_linear_regression(m=5, n_per=30, d=20, seed=0)
    a = b.alpha_paper
    grid = sweep.ConfigGrid(alpha=(a,), beta=(0.4,), eps1_scale=(0.1, 1.0),
                            seed=(0, 1), num_workers=(5,))
    res = sweep.run_sweep(grid, task_factory=_task_factory, num_iters=60)
    assert len(res) == 4 and res.num_programs == 2
    for p, hist in zip(res.points, res.histories):
        cfg = chb.FedOptConfig(alpha=p.alpha, beta=p.beta, eps1=p.eps1,
                               num_workers=5)
        ref = simulator.run(cfg, _task_factory(p.seed, 5), 60)
        exactness.assert_bitwise(hist, ref)


def test_sweep_seed_axis_requires_factory(linreg):
    grid = sweep.ConfigGrid(alpha=(linreg.alpha_paper,), seed=(0, 1))
    with pytest.raises(ValueError, match="task_factory"):
        sweep.run_sweep(grid, task=linreg.task, num_iters=5)
    # a single non-default seed with a shared task would silently mislabel
    # every result row — must be an error, not a shrug
    pts = [sweep.GridPoint(alpha=linreg.alpha_paper, seed=3)]
    with pytest.raises(ValueError, match="task_factory"):
        sweep.run_sweep(pts, task=linreg.task, num_iters=5)


def test_sweep_per_tensor_granularity_exact(linreg):
    """Per-tensor censoring sweeps too: eps1 becomes a static partition
    axis (its byte accounting divmods host-side), and every point stays
    bit-exact vs the per-point simulator run."""
    from repro import opt
    a = linreg.alpha_paper
    base = opt.make("chb", a, 5, granularity="per_tensor")
    eps = paper_eps1(a, 5)
    points = [sweep.GridPoint(alpha=a, beta=0.4, eps1=eps),
              sweep.GridPoint(alpha=a, beta=0.4, eps1=2 * eps),
              sweep.GridPoint(alpha=a * 0.5, beta=0.4, eps1=eps)]
    res = sweep.run_sweep(points, task=linreg.task, num_iters=80,
                          base_cfg=base)
    assert res.num_programs == 2      # one per distinct static eps1
    for p, hist in zip(points, res.histories):
        ref = simulator.run(
            opt.make("chb", p.alpha, 5, beta=p.beta, eps1=p.eps1,
                     granularity="per_tensor"), linreg.task, 80)
        exactness.assert_bitwise(hist, ref)
    # per-tensor masks really differ from global censoring on this grid
    ref_global = simulator.run(opt.make("chb", a, 5, eps1=eps),
                               linreg.task, 80)
    assert (np.asarray(res.histories[0].mask)
            != np.asarray(ref_global.mask)).any()


def test_sweep_float32_task_exact_under_x64():
    """Bit-exactness must hold for f32 tasks too: traced alpha/beta arrive
    as strong f64 scalars under x64 and used to promote (and double-round)
    the f32 eq.-(4) update, flipping censor decisions vs simulator.run."""
    b = paper_tasks.make_linear_regression(m=4, n_per=20, d=10, seed=0)
    to32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    task32 = b.task._replace(init_params=to32(b.task.init_params),
                             worker_data=to32(b.task.worker_data))
    cfg = baselines.chb(b.alpha_paper, 4)
    res = sweep.run_sweep(
        [sweep.GridPoint(alpha=cfg.alpha, beta=cfg.beta, eps1=cfg.eps1)],
        task=task32, num_iters=200)
    exactness.assert_bitwise(res.history(0), simulator.run(cfg, task32, 200))


def test_transmit_mask_traced_eps_matches_static_at_f32_boundary():
    """The eq.-(8) decision must be f32 for static AND traced eps1.

    dsq = f32(0.3) sits exactly on the f32 censor boundary for eps1=0.1,
    ssq=3: in f32 arithmetic eps1*ssq == dsq (censored), in f64 it is
    strictly smaller (transmit). A traced f64 eps1 used to flip this
    decision, breaking the sweep engine's bit-exactness contract."""
    from repro.core.censoring import transmit_mask
    dsq = jnp.float32(0.3)
    ssq = jnp.float32(3.0)
    static = transmit_mask(dsq, ssq, 0.1)
    traced = jax.jit(lambda e: transmit_mask(dsq, ssq, e))(
        jnp.asarray(0.1, jnp.float64))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))
    assert float(static) == 0.0     # f32 semantics: censored


def test_sweep_nn_pytree_task():
    """Pytree (dict) parameters work through the engine unchanged."""
    b = paper_tasks.make_neural_network(m=4, n_per=40, d=8, hidden=6)
    cfg = baselines.chb(0.02, 4)
    pts = [sweep.GridPoint(alpha=cfg.alpha, beta=cfg.beta, eps1=cfg.eps1),
           sweep.GridPoint(alpha=cfg.alpha / 2, beta=0.0, eps1=0.0)]
    res = sweep.run_sweep(pts, task=b.task, num_iters=25)
    for p, hist in zip(pts, res.histories):
        c = chb.FedOptConfig(alpha=p.alpha, beta=p.beta, eps1=p.eps1,
                             num_workers=4)
        exactness.assert_bitwise(hist, simulator.run(c, b.task, 25))


def test_sweep_vectorized_mode_close(linreg):
    """vectorize=True batches the matmuls: same trajectories to float
    tolerance (bit-exactness is only contracted for the default mode)."""
    a = linreg.alpha_paper
    cfg = baselines.chb(a, 5)
    pts = [sweep.GridPoint(alpha=cfg.alpha, beta=cfg.beta, eps1=cfg.eps1),
           sweep.GridPoint(alpha=cfg.alpha, beta=0.0, eps1=0.0)]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=80,
                          vectorize=True)
    for p, hist in zip(pts, res.histories):
        c = chb.FedOptConfig(alpha=p.alpha, beta=p.beta, eps1=p.eps1,
                             num_workers=5)
        ref = simulator.run(c, linreg.task, 80)
        exactness.assert_close(hist.objective, ref.objective, "vectorized")


def test_traced_structural_fields_raise(linreg):
    """Structural config fields must stay static: a traced adaptive is a
    loud error, not silent miscompilation."""
    cfg = chb.FedOptConfig(alpha=0.1, num_workers=5, adaptive=0.5)

    def bad(adaptive):
        c = chb.FedOptConfig(alpha=0.1, num_workers=5, adaptive=adaptive)
        return simulator.trajectory(c, linreg.task, 2).objective

    with pytest.raises(NotImplementedError, match="adaptive"):
        jax.jit(bad)(jnp.asarray(0.5))
    # static adaptive still works through the (non-sweep) path
    hist = simulator.run(cfg, linreg.task, 10)
    assert int(hist.final_state.comm.iterations) == 10


# ----------------------------------------------------- frontier + export
def test_frontier_and_export_roundtrip(linreg, tmp_path):
    a = linreg.alpha_paper
    cfgs = [baselines.ALGORITHMS[n](a, 5) for n in ("gd", "chb")]
    pts = [sweep.GridPoint(alpha=c.alpha, beta=c.beta, eps1=c.eps1)
           for c in cfgs]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=400)
    fstar = float(simulator.estimate_fstar(linreg.task, a, 8000))
    rows = res.frontier(fstar, 1e-6)
    assert [r["algo"] for r in rows] == ["gd", "chb"]
    assert all(r["iters_to_tol"] > 0 for r in rows)
    assert rows[1]["total_comms"] < rows[0]["total_comms"]  # CHB censors

    jpath, cpath = tmp_path / "s.json", tmp_path / "s.csv"
    res.to_json(str(jpath), fstar=fstar, tol=1e-6)
    doc = json.loads(jpath.read_text())
    assert doc["num_points"] == 2 and len(doc["objective"]) == 2
    assert doc["frontier"][1]["algo"] == "chb"
    res.to_csv(fstar, 1e-6, str(cpath))
    lines = cpath.read_text().splitlines()
    assert lines[0].startswith("index,algo,") and len(lines) == 3


# ------------------------------------------------------------- fed sweep
def test_fed_sweep_ideal_point_matches_run(linreg):
    """loss 0 + participation 1 + quorum 1 == core/simulator.run exactly
    (the same anchor contract as the event-driven fed runtime)."""
    cfg = baselines.chb(linreg.alpha_paper, 5)
    grid = sweep.FedScenarioGrid(loss_prob=(0.0, 0.4))
    res = sweep.run_fed_sweep(cfg, linreg.task, grid, num_rounds=80)
    ref = simulator.run(cfg, linreg.task, 80)
    i = res.points.index(sweep.FedScenarioPoint(0.0, 1.0, 1.0, 0))
    np.testing.assert_array_equal(res.objective[i],
                                  np.asarray(ref.objective))
    np.testing.assert_array_equal(res.comm_cum[i], np.asarray(ref.comm_cum))
    np.testing.assert_array_equal(
        res.transmit_mask[i], np.asarray(ref.mask).astype(np.int8))
    assert bool(res.quorum_met[i].all())


def test_fed_sweep_scenario_effects(linreg):
    cfg = baselines.chb(linreg.alpha_paper, 5)
    grid = sweep.FedScenarioGrid(loss_prob=(0.0, 0.4),
                                 participation=(1.0, 0.5))
    res = sweep.run_fed_sweep(cfg, linreg.task, grid, num_rounds=120)
    p = list(res.points)
    ideal = p.index(sweep.FedScenarioPoint(0.0, 1.0, 1.0, 0))
    lossy = p.index(sweep.FedScenarioPoint(0.4, 1.0, 1.0, 0))
    partial = p.index(sweep.FedScenarioPoint(0.0, 0.5, 1.0, 0))
    # drops burn uplinks without delivering
    assert res.delivered_cum[lossy, -1] < res.comm_cum[lossy, -1]
    assert res.delivered_cum[ideal, -1] == res.comm_cum[ideal, -1]
    # partial participation attempts fewer uplinks than full
    assert res.comm_cum[partial, -1] < res.comm_cum[ideal, -1]
    # accounting is monotone and consistent
    assert (np.diff(res.energy_cum, axis=1) >= 0).all()
    assert (res.bytes_cum[:, -1] > 0).all()
    fstar = float(simulator.estimate_fstar(linreg.task,
                                           linreg.alpha_paper, 8000))
    rows = res.frontier(fstar, 1e-6)
    assert rows[ideal]["rounds"] > 0 and rows[ideal]["energy_j"] > 0


def test_fed_sweep_rejects_unsupported_modes(linreg):
    import dataclasses
    cfg = dataclasses.replace(baselines.chb(linreg.alpha_paper, 5),
                              quantize="int8")
    with pytest.raises(NotImplementedError):
        sweep.run_fed_sweep(cfg, linreg.task, sweep.FedScenarioGrid(), 5)


# ------------------------------------------------------ benchmark driver
def test_bench_run_only_unknown_name_exits_nonzero():
    """A typo'd --only must fail fast listing valid names, not print an
    empty CSV with exit 0."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "no_such_bench"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no_such_bench" in proc.stderr
    assert "fig11_epsilon" in proc.stderr     # the valid names are listed

"""The ``backend`` axis: the Pallas kernels as the opt execution engine.

Pins the tentpole contract of the backend redesign:

  * ``opt.make(name, backend="pallas")`` runs end-to-end through
    ``simulator.run``, ``sweep.run_sweep`` and the ``repro.fed`` event
    runtime, computing the same run as the reference backend at f32 and
    f64 (in interpret mode on the CPU) — held by ``exactness``'s
    two-program contract and by the recorded pins;
  * specs round-trip the backend through JSON;
  * sweeping (alpha, beta, eps1) over a pallas composition compiles ONE
    program and traces each kernel dispatch exactly once (the
    static-hparam retrace bug this PR fixes made every point recompile);
  * compositions the kernels cannot fuse (custom stages) are rejected at
    construction instead of silently falling back.
"""
import json

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import fed, opt, sweep
from repro.core import simulator
from repro.data import paper_tasks
from repro.kernels import ops as kernel_ops

M = 5
ITERS = 60


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=M, n_per=30, d=20, seed=0)


def _cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def _as_f32(task):
    return task._replace(init_params=_cast_tree(task.init_params,
                                                jnp.float32),
                         worker_data=_cast_tree(task.worker_data,
                                                jnp.float32))


@pytest.fixture(scope="module")
def task32(linreg):
    return _as_f32(linreg.task)


# Transport hyperparameters of the ``f32/<kind>`` pins: k=8 actually
# sparsifies the d=20 task. A transport registered without a pin FAILS
# ``test_golden_fingerprints_all_transports`` loudly instead of going
# uncovered.
GOLDEN_TRANSPORT_KW = {"topk": {"k": 8}, "lowrank": {"rank": 2}}


# ------------------------------------------------------- simulator parity
@pytest.mark.parametrize("name,kw", [
    ("gd", {}), ("hb", {}), ("lag", {}), ("chb", {}),
    ("csgd", {"tau0": 5.0}),
    ("chb", {"quantize": "int8"}),
    ("chb", {"granularity": "per_tensor"}),
])
def test_simulator_bitwise_f32(linreg, task32, name, kw):
    o_ref = opt.make(name, linreg.alpha_paper, M, **kw)
    o_pal = opt.make(name, linreg.alpha_paper, M, backend="pallas", **kw)
    exactness.assert_same_run(simulator.run(o_ref, task32, ITERS),
                              simulator.run(o_pal, task32, ITERS))


@pytest.mark.parametrize("kw", [{}, {"quantize": "int8"}])
def test_simulator_bitwise_f64(linreg, kw):
    o_ref = opt.make("chb", linreg.alpha_paper, M, **kw)
    o_pal = opt.make("chb", linreg.alpha_paper, M, backend="pallas", **kw)
    exactness.assert_same_run(simulator.run(o_ref, linreg.task, ITERS),
                              simulator.run(o_pal, linreg.task, ITERS))


def test_golden_fingerprints_both_backends(linreg, task32):
    """Both backends reproduce the recorded run.

    The pallas leg runs the one-sweep fused step (its default route), so
    this pin also holds the megakernel to the reference's run."""
    for backend in opt.BACKENDS:
        o = opt.make("chb", linreg.alpha_paper, M, backend=backend)
        exactness.assert_matches_pin(
            simulator.run(o, task32, ITERS, collect_metrics=True),
            exactness.PINS["f32/dense"])


@pytest.mark.parametrize("kind", ["dense", "int8"])
def test_golden_fingerprints_staged_pallas(linreg, task32, kind):
    """``force_staged()`` holds the pre-fusion kernel chain to the SAME
    pins: the fused and staged pallas routes may never drift apart."""
    from repro.kernels import fused_step
    t = opt.make_transport(kind)
    o = opt.make("chb", linreg.alpha_paper, M, transport=t,
                 backend="pallas")
    with fused_step.force_staged():
        h = simulator.run(o, task32, ITERS, collect_metrics=True)
    exactness.assert_matches_pin(h, exactness.PINS[f"f32/{kind}"])


@pytest.mark.parametrize("kind", sorted(opt.TRANSPORT_KINDS))
def test_golden_fingerprints_all_transports(linreg, task32, kind):
    """Every registered transport has a pin, reproduced by BOTH backends.
    A new registry entry without a pin fails the first assert — record
    one instead of shipping an uncovered transport."""
    assert f"f32/{kind}" in exactness.PINS, (
        f"transport {kind!r} is registered but has no pin; add an "
        f"exactness.PINS['f32/{kind}'] entry (and GOLDEN_TRANSPORT_KW "
        "hyperparameters if the defaults are a no-op on the d=20 task)")
    t = opt.make_transport(kind, **GOLDEN_TRANSPORT_KW.get(kind, {}))
    for backend in opt.BACKENDS:
        o = opt.make("chb", linreg.alpha_paper, M, transport=t,
                     backend=backend)
        exactness.assert_matches_pin(
            simulator.run(o, task32, ITERS, collect_metrics=True),
            exactness.PINS[f"f32/{kind}"])


@pytest.mark.parametrize("kind,pin", [("dense", "int8"), ("int8", "dense"),
                                      ("topk", "dense")])
def test_golden_fingerprint_rejects_another_transport(linreg, task32, kind,
                                                      pin):
    """A transport's run fails another transport's pin: dense and int8
    take the same decisions before the floor and part on the uplink
    bytes; top-k parts on its uplinks."""
    t = opt.make_transport(kind, **GOLDEN_TRANSPORT_KW.get(kind, {}))
    o = opt.make("chb", linreg.alpha_paper, M, transport=t)
    h = simulator.run(o, task32, ITERS, collect_metrics=True)
    with pytest.raises(AssertionError):
        exactness.assert_matches_pin(h, exactness.PINS[f"f32/{pin}"])


def test_pytree_task_bitwise(linreg):
    bn = paper_tasks.make_neural_network(m=4, n_per=40, d=8, hidden=6)
    t32 = _as_f32(bn.task)
    exactness.assert_same_run(
        simulator.run(opt.make("chb", 0.02, 4), t32, 25),
        simulator.run(opt.make("chb", 0.02, 4, backend="pallas"), t32, 25))


# ------------------------------------------------------------ spec axis
def test_spec_roundtrips_backend(linreg):
    o = opt.make("chb", 0.05, M, backend="pallas")
    spec = opt.to_spec(o)
    assert spec["backend"] == "pallas"
    assert opt.from_spec(spec) == o
    # JSON wire round-trip
    assert opt.from_spec(json.loads(json.dumps(spec))) == o
    # pre-backend specs (no key) rebuild on the reference backend
    legacy = {k: v for k, v in spec.items() if k != "backend"}
    assert opt.from_spec(legacy).backend == "reference"


def test_with_hparams_preserves_backend():
    o = opt.make("chb", 0.05, M, backend="pallas")
    o2 = o.with_hparams(alpha=0.1, beta=0.3, eps1=2.0)
    assert o2.backend == "pallas"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        opt.make("chb", 0.05, M, backend="mosaic")


def test_custom_stages_rejected_on_pallas():
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class MyServer:
        alpha: float

        def apply(self, params, prev_params, agg):
            return params

    with pytest.raises(TypeError, match="custom server"):
        opt.ComposedOptimizer(
            censor=opt.NeverCensor(), transport=opt.DenseTransport(),
            server=MyServer(0.1), num_workers=M, backend="pallas")


# --------------------------------------------------------- sweep engine
def test_sweep_pallas_bitwise_one_program(linreg, task32):
    grid = sweep.ConfigGrid(
        alpha=[0.5 * linreg.alpha_paper, linreg.alpha_paper],
        beta=[0.0, 0.4], eps1=[0.5, 2.0])
    base_p = opt.make("chb", linreg.alpha_paper, M, backend="pallas")
    base_r = opt.make("chb", linreg.alpha_paper, M)
    kernel_ops.reset_trace_counts()
    res_p = sweep.run_sweep(grid, task32, num_iters=40, base_cfg=base_p)
    # one compiled program for the whole 8-point grid, each kernel
    # dispatch traced exactly once (the retrace-bug regression)
    assert res_p.num_programs == 1
    assert kernel_ops.trace_counts == {"tree_delta_sqnorms": 1,
                                       "tree_fused_dense_step": 1}
    res_r = sweep.run_sweep(grid, task32, num_iters=40, base_cfg=base_r)
    for i in range(len(res_p)):
        exactness.assert_same_run(res_p.history(i), res_r.history(i))
        assert res_p.specs[i]["backend"] == "pallas"
        assert res_r.specs[i]["backend"] == "reference"
    # sweep rows == per-point pallas simulator.run (the PR-2 exactness
    # contract, now holding for the kernel backend too; asserted on the
    # f64 task — at f32 it holds only to the ulp for BOTH backends)
    res64 = sweep.run_sweep(grid, linreg.task, num_iters=40,
                            base_cfg=base_p)
    pt = res64.points[3]
    o = base_p.with_hparams(alpha=pt.alpha, beta=pt.beta, eps1=pt.eps1)
    exactness.assert_bitwise(simulator.run(o, linreg.task, 40),
                             res64.history(3))


# ----------------------------------------------------------- fed runtime
def test_fed_pallas_bitwise(linreg):
    """Event runtime, sync anchor: pallas == reference, bit-for-bit."""
    edge = fed.sync_config(M)
    for kw in ({}, {"quantize": "int8"}):
        h_ref = fed.run_edge(opt.make("chb", linreg.alpha_paper, M, **kw),
                             linreg.task, edge, 30)
        h_pal = fed.run_edge(
            opt.make("chb", linreg.alpha_paper, M, backend="pallas", **kw),
            linreg.task, edge, 30)
        exactness.assert_bitwise(h_ref, h_pal)


def test_fed_pallas_sync_anchor(linreg):
    """Pallas fed == pallas simulator on the sync anchor: draws, masks
    and uplinks exact; objectives within ``exactness.BOUNDS``'s
    ``event_runtime`` (per-row gradients there, vmapped here)."""
    o = opt.make("csgd", linreg.alpha_paper, M, tau0=5.0,
                 backend="pallas")
    hs = simulator.run(o, linreg.task, 25)
    he = fed.run_edge(o, linreg.task, fed.sync_config(M), 25)
    exactness.assert_close_run(he, hs, "event_runtime")


# -------------------------------------------------- multi-tile numerics
def test_multitile_masks_aligned_trajectories_close():
    """Beyond the golden scale (multi-tile leaves, >256*128 elements per
    worker): censor masks and uplink counts stay aligned between the
    backends, trajectories stay close but may drift by compounded
    fusion/reduction ulps — the documented contract limit
    (docs/kernels.md), pinned here so a real kernel bug (which would
    break masks or blow past ulp scale) cannot hide behind it."""
    m, d = 4, 70_000
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (m, 30, d), jnp.float32) * 0.05
    y = jax.random.normal(jax.random.fold_in(key, 1), (m, 30), jnp.float32)
    task = simulator.FedTask(
        init_params=jnp.zeros((d,), jnp.float32),
        grad_fn=lambda p, dat: (dat[0].T @ (dat[0] @ p - dat[1]))
        / dat[0].shape[0],
        loss_fn=lambda p, dat: 0.5 * jnp.mean((dat[0] @ p - dat[1]) ** 2),
        worker_data=(A, y), name="multitile")
    h_ref = simulator.run(opt.make("chb", 0.05, m, eps1=0.3), task, 25)
    h_pal = simulator.run(opt.make("chb", 0.05, m, eps1=0.3,
                                   backend="pallas"), task, 25)
    exactness.assert_close_run(h_ref, h_pal, "multitile")


# ----------------------------------------------------- distributed hook
def test_distributed_accepts_pallas_composition(linreg):
    """The scan strategy consumes the composition's hyperparameter views;
    a pallas composition passes realizability and trains."""
    from repro.core import distributed
    o = opt.make("chb", 0.05, 4, backend="pallas")
    params = {"w": jnp.zeros((8,), jnp.float32)}
    data = (jnp.ones((4, 3, 8), jnp.float32),
            jnp.ones((4, 3), jnp.float32))

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    state = distributed.init_scan_state(o, params)
    step = jax.jit(distributed.make_scan_step(o, loss_fn))
    params2, state2, metrics = step(params, state, data)
    assert np.isfinite(float(metrics["loss"]))
    assert int(np.asarray(state2.step)) == 1

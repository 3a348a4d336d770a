"""The mesh-sharded federated runtime's single-shard contracts.

Anchor (a) lives here: ``fed.run_mesh`` sharded over ONE device under the
ideal scenario computes the same run as ``core.simulator.run`` — censor
masks, uplink counts, objective and final params bitwise, the aggregate
norm to ``exactness``'s diagnostic bound — across algorithms, backends,
and transports. Everything multi-device (anchor (b), K-shard invariance)
runs in subprocesses in tests/test_distributed.py.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import fed, opt
from repro.core import simulator
from repro.data import edge_tasks, paper_tasks
from repro.fed.clients import uniform_vector_population
from repro.fed.mesh import MeshScenario, run_mesh
from repro.launch.mesh import make_client_mesh

M = 5


@pytest.fixture(scope="module")
def bundle():
    return paper_tasks.make_linear_regression(m=M, n_per=30, d=20, seed=0)


@pytest.mark.parametrize("backend", sorted(opt.BACKENDS))
@pytest.mark.parametrize("algo", ["chb", "lag", "csgd"])
def test_sync_anchor_bitwise_dense(bundle, algo, backend):
    """Ideal scenario, K=1: run_mesh computes simulator.run's run."""
    o = opt.make(algo, bundle.alpha_paper, M, backend=backend)
    hist = simulator.run(o, bundle.task, 12)
    mh = run_mesh(o, bundle.task, 12)
    exactness.assert_same_run(hist, mh)
    assert mh.quorum_met.all()
    assert (mh.participated == M).all()
    np.testing.assert_array_equal(mh.attempted, mh.delivered)


@pytest.mark.parametrize("backend", sorted(opt.BACKENDS))
def test_sync_anchor_bitwise_int8(bundle, backend):
    """The quantized transport rides the same anchor: shard_step's staged
    kernels must reproduce the fused step's bits through EF residuals."""
    o = opt.make("chb", bundle.alpha_paper, M, quantize="int8",
                 backend=backend)
    exactness.assert_bitwise(simulator.run(o, bundle.task, 12),
                             run_mesh(o, bundle.task, 12))


def test_donation_is_bit_identical(bundle):
    """``donate=True`` may only change buffer reuse, never a rounding —
    including the prev_params overwrite after a quorum round."""
    o = opt.make("chb", bundle.alpha_paper, M)
    sc = MeshScenario(participation=0.7, loss_prob=0.3, quorum=0.6, seed=5)
    plain = run_mesh(o, bundle.task, 15, scenario=sc)
    donated = run_mesh(o, bundle.task, 15, scenario=sc, donate=True)
    exactness.assert_bitwise(plain, donated)


def test_bake_data_off_is_allclose_not_required_bitwise(bundle):
    """``bake_data=False`` (argument-passed data, one shared trace) stays
    within reduction-order ulps of the baked default; masks and counts
    are exactly equal (integer decisions survive the ulp)."""
    o = opt.make("chb", bundle.alpha_paper, M)
    sc = MeshScenario(participation=0.8, loss_prob=0.1, seed=2)
    baked = run_mesh(o, bundle.task, 12, scenario=sc)
    unbaked = run_mesh(o, bundle.task, 12, scenario=sc, bake_data=False)
    exactness.assert_close_run(baked, unbaked, "argument_data")


def test_scenario_draws_replay_exactly(bundle):
    """Same scenario → same draws, run to run: the per-(seed, round, id)
    key folding has no hidden state."""
    o = opt.make("chb", bundle.alpha_paper, M)
    sc = MeshScenario(participation=0.6, loss_prob=0.25, seed=11)
    a = run_mesh(o, bundle.task, 10, scenario=sc)
    b = run_mesh(o, bundle.task, 10, scenario=sc)
    exactness.assert_bitwise(a, b)
    # and a different seed actually changes the draws
    c = run_mesh(o, bundle.task, 10,
                 scenario=MeshScenario(participation=0.6, loss_prob=0.25,
                                       seed=12))
    assert not np.array_equal(a.mask, c.mask)


def test_quorum_semantics_pinned_by_counts(bundle):
    """Replay fed_sweep's quorum rule from the recorded counts: met iff
    ``arrived >= ceil(quorum * cohort)`` with censored beacons counting
    and drops not; frozen rounds freeze the objective."""
    o = opt.make("chb", bundle.alpha_paper, M)
    sc = MeshScenario(participation=0.8, loss_prob=0.4, quorum=0.7, seed=7)
    mh = run_mesh(o, bundle.task, 30, scenario=sc)
    arrived = mh.participated - (mh.attempted - mh.delivered)
    want = (arrived >= np.ceil(sc.quorum * mh.participated)) \
        & (mh.participated > 0)
    np.testing.assert_array_equal(mh.quorum_met, want)
    assert not mh.quorum_met.all(), "scenario too easy to pin the gate"
    # a failed round k freezes theta, so round k+1 re-evaluates the same
    # objective value
    frozen = np.nonzero(~mh.quorum_met[:-1])[0]
    np.testing.assert_array_equal(mh.objective[frozen + 1],
                                  mh.objective[frozen])
    assert (mh.delivered <= mh.attempted).all()
    assert (mh.attempted <= mh.participated).all()


def test_quorum_need_is_the_shared_definition():
    """One quorum definition across the event runtime and the mesh."""
    assert fed.quorum_need(1.0, 7) == 7
    assert fed.quorum_need(0.5, 7) == 4
    assert fed.quorum_need(0.2, 3) == 1
    assert fed.quorum_need(0.1, 0) == 1   # floor: never wait on nobody


def test_accounting_bytes_energy_wall(bundle):
    """Bytes are exact attempted×payload ints; energy and wall-clock are
    monotone and follow the shared EnergyModel.round_energy split."""
    o = opt.make("chb", bundle.alpha_paper, M)
    sc = MeshScenario(participation=0.7, loss_prob=0.2, seed=3)
    pop = uniform_vector_population(M, compute_mean_s=0.5,
                                   straggler_frac=0.2)
    ch = fed.ChannelConfig()
    em = fed.EnergyModel()
    mh = run_mesh(o, bundle.task, 10, scenario=sc, population=pop,
                  channel=ch, energy=em)
    payload = o.transport.payload_bytes(bundle.task.init_params)
    np.testing.assert_array_equal(mh.bytes_cum,
                                  np.cumsum(mh.attempted) * payload)
    np.testing.assert_array_equal(mh.comm_cum, np.cumsum(mh.attempted))
    assert (np.diff(mh.wall_clock) > 0).all()
    assert (np.diff(mh.energy_cum) > 0).all()
    # radio joules alone lower-bound the total (compute joules are >= 0)
    radio = np.cumsum(em.round_energy(mh.attempted, mh.participated,
                                      payload))
    assert (mh.energy_cum >= radio - 1e-9).all()


def test_collect_metrics_merges_to_simulator_bag(bundle):
    """K=1 sync: the merged per-round MetricBag equals the simulator's
    (weighted mean over one shard is the identity)."""
    o = opt.make("chb", bundle.alpha_paper, M)
    hist = simulator.run(o, bundle.task, 8, collect_metrics=True)
    mh = run_mesh(o, bundle.task, 8, collect_metrics=True)
    assert len(mh.metrics) == 8
    for k in ("censor_rate", "bank_sqnorm", "agg_grad_sqnorm",
              "step_sqnorm"):
        sim_series = np.asarray(hist.metrics[k])
        mesh_series = np.asarray([bag[k] for bag in mh.metrics])
        exactness.assert_close(mesh_series, sim_series, "mesh_metrics",
                               err_msg=k)


def test_rejects_non_composed_and_adaptive(bundle):
    import dataclasses as dc

    from repro.opt.api import FedOptimizer

    @dc.dataclass(frozen=True)
    class Wrapped(FedOptimizer):
        inner: object

        def init(self, params):
            return self.inner.init(params)

        def step(self, state, params, grads):
            return self.inner.step(state, params, grads)

    with pytest.raises(TypeError, match="ComposedOptimizer"):
        run_mesh(Wrapped(opt.make("chb", bundle.alpha_paper, M)),
                 bundle.task, 2)
    adaptive = opt.ComposedOptimizer(
        censor=opt.AdaptiveCensor(0.25), transport=opt.DenseTransport(),
        server=opt.HeavyBall(bundle.alpha_paper, 0.4), num_workers=M)
    with pytest.raises(NotImplementedError, match="adaptive"):
        run_mesh(adaptive, bundle.task, 2)


def test_rejects_mismatched_sizes(bundle):
    o = opt.make("chb", bundle.alpha_paper, M + 1)
    with pytest.raises(ValueError, match="num_workers"):
        run_mesh(o, bundle.task, 2)
    o = opt.make("chb", bundle.alpha_paper, M)
    with pytest.raises(ValueError, match="clients"):
        run_mesh(o, bundle.task, 2,
                 population=uniform_vector_population(M + 2))


def test_mesh_larger_than_device_count_raises_loudly():
    """The single-device pytest process cannot host a 2-shard mesh — the
    error must name the XLA_FLAGS escape hatch, not crash in XLA."""
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_client_mesh(2)


def test_scenario_validation():
    with pytest.raises(ValueError, match="participation"):
        MeshScenario(participation=0.0)
    with pytest.raises(ValueError, match="loss_prob"):
        MeshScenario(loss_prob=1.0)
    with pytest.raises(ValueError, match="quorum"):
        MeshScenario(quorum=1.5)
    assert MeshScenario().sync_draws
    assert not MeshScenario(participation=0.9).sync_draws
    assert not MeshScenario(loss_prob=0.1).sync_draws


def test_vector_population_shapes_and_conversion():
    pop = uniform_vector_population(10, straggler_frac=0.3, seed=1)
    assert pop.num_clients == 10
    assert pop.compute_mean_s.shape == (10,)
    assert (pop.compute_mean_s > 0).all()
    from repro.fed.clients import uniform_population
    vec = uniform_population(4).as_vector()
    assert vec.num_clients == 4
    with pytest.raises(ValueError):
        fed.VectorPopulation(np.ones(3), np.ones(4))
    with pytest.raises(ValueError):
        fed.VectorPopulation(np.ones(3), np.ones(3), participation=0.0)


def test_edge_quadratics_task():
    """The O(M·d) ladder task: grads match autodiff, f* is closed-form,
    and the mesh runtime drives it to the optimum."""
    task = edge_tasks.make_edge_quadratics(64, d=8, seed=4)
    theta = jnp.linspace(-1.0, 1.0, 8)
    row = jax.tree_util.tree_map(lambda x: x[3], task.worker_data)
    auto = jax.grad(task.loss_fn)(theta, row)
    np.testing.assert_allclose(np.asarray(task.grad_fn(theta, row)),
                               np.asarray(auto), rtol=1e-12)
    fstar = edge_tasks.edge_quadratics_fstar(task)
    o = opt.make("csgd", 1.0 / 64, 64)
    mh = run_mesh(o, task, 60, collect_mask=False)
    assert mh.objective[-1] - fstar < 1e-3 * mh.objective[0]
    assert mh.mask is None


def test_edge_linreg_task_runs():
    task = edge_tasks.make_edge_linreg(32, n_per=4, d=8, seed=2)
    o = opt.make("chb", 1.0 / (32 * 4), 32)
    mh = run_mesh(o, task, 40)
    assert mh.objective[-1] < mh.objective[0]
    assert np.isfinite(mh.objective).all()

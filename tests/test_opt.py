"""repro.opt: the composable optimizer protocol.

Pins (1) the trajectory of every legacy composition against the runs the
pre-redesign monolithic ``chb.step`` recorded (``exactness.PINS``), (2)
registry round-trips and error behavior, (3) the deprecation shims, (4)
csgd — a pure composition — end-to-end through simulator, fed runtime, and
sweep, and (5) censor-mask properties (hypothesis).
"""
import json
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import fed, opt, sweep
from repro.core import baselines, chb, simulator
from repro.data import paper_tasks


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=5, n_per=30, d=20, seed=0)


def test_task_alpha_unchanged(linreg):
    """The pins assume this task; if alpha moves, they mean nothing."""
    assert float(linreg.alpha_paper) == exactness.LINREG_ALPHA_PAPER


# ------------------------------------------------------ pinned trajectories
@pytest.mark.parametrize("name", ["gd", "hb", "lag", "chb"])
def test_registry_matches_pre_redesign_step(linreg, name):
    h = simulator.run(opt.make(name, linreg.alpha_paper, 5), linreg.task, 80,
                      collect_metrics=True)
    exactness.assert_matches_pin(h, exactness.PINS[name])


def test_int8_composition_matches_pre_redesign(linreg):
    o = opt.make("chb", linreg.alpha_paper, 5, quantize="int8")
    exactness.assert_matches_pin(
        simulator.run(o, linreg.task, 80, collect_metrics=True),
        exactness.PINS["chb_int8"])


def test_per_tensor_composition_matches_pre_redesign(linreg):
    o = opt.make("chb", linreg.alpha_paper, 5, granularity="per_tensor")
    exactness.assert_matches_pin(
        simulator.run(o, linreg.task, 80, collect_metrics=True),
        exactness.PINS["chb_per_tensor"])


def test_adaptive_composition_matches_pre_redesign(linreg):
    o = opt.ComposedOptimizer(
        censor=opt.AdaptiveCensor(0.25), transport=opt.DenseTransport(),
        server=opt.HeavyBall(linreg.alpha_paper, 0.4), num_workers=5)
    exactness.assert_matches_pin(
        simulator.run(o, linreg.task, 80, collect_metrics=True),
        exactness.PINS["adaptive"])


def test_pytree_task_matches_pre_redesign():
    # the pin's init was drawn before jax made threefry partitionable by
    # default; the same key draws another init in that mode
    with jax.threefry_partitionable(False):
        bn = paper_tasks.make_neural_network(m=4, n_per=40, d=8, hidden=6)
    h = simulator.run(opt.make("chb", 0.02, 4), bn.task, 25,
                      collect_metrics=True)
    exactness.assert_matches_pin(h, exactness.PINS["nn_chb"])


@pytest.mark.parametrize("kw,pin", [
    ({}, "chb_per_tensor"), ({"granularity": "per_tensor"}, "chb"),
    ({}, "chb_int8"), ({"quantize": "int8"}, "chb"),
])
def test_pin_rejects_a_neighbouring_route(linreg, kw, pin):
    """A pin tells its route from the nearest other one: global from
    per-tensor censoring (the uplinks part at iteration 42, before either
    floor), dense from int8 (the uplink bytes)."""
    h = simulator.run(opt.make("chb", linreg.alpha_paper, 5, **kw),
                      linreg.task, 80, collect_metrics=True)
    with pytest.raises(AssertionError):
        exactness.assert_matches_pin(h, exactness.PINS[pin])


# ------------------------------------------------------ deprecation shims
def test_fedoptconfig_construction_warns():
    with pytest.warns(DeprecationWarning, match="repro.opt"):
        chb.FedOptConfig(alpha=0.1, num_workers=3)


@pytest.mark.parametrize("name", ["gd", "hb", "lag", "chb"])
def test_baselines_warn_and_match_registry_bitwise(linreg, name):
    """The legacy constructors warn once and build the SAME composition
    the registry does — trajectories bit-for-bit identical."""
    with pytest.warns(DeprecationWarning):
        cfg = baselines.ALGORITHMS[name](linreg.alpha_paper, 5)
    built = cfg.build()
    reg = opt.make(name, linreg.alpha_paper, 5)
    # the facade may express "no censoring"/"no momentum" through the same
    # stages or degenerate ones; the trajectories must be bit-identical
    h_facade = simulator.run(cfg, linreg.task, 60)
    h_built = simulator.run(built, linreg.task, 60)
    h_reg = simulator.run(reg, linreg.task, 60)
    exactness.assert_bitwise(h_facade, h_reg)
    exactness.assert_bitwise(h_built, h_reg)


def test_legacy_step_entrypoint_still_works(linreg):
    """chb.init/chb.step keep their legacy signatures and return order."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cfg = baselines.chb(linreg.alpha_paper, 5)
    params = linreg.task.init_params
    state = chb.init(cfg, params)
    grads = jax.vmap(linreg.task.grad_fn, in_axes=(None, 0))(
        params, linreg.task.worker_data)
    new_params, new_state, info = chb.step(cfg, state, params, grads)
    assert isinstance(info, opt.StepStats)
    assert isinstance(new_state, opt.OptState)
    assert info.mask.shape == (5,)
    assert int(new_state.comm.iterations) == 1
    assert jax.tree_util.tree_structure(new_params) == \
        jax.tree_util.tree_structure(params)


# -------------------------------------------------- registry round-trips
def test_spec_roundtrip_identity_all_registered():
    for name in opt.names():
        o = opt.make(name, 0.05, 4)
        spec = opt.to_spec(o)
        assert opt.from_spec(spec) == o, name
        # and through an actual JSON wire format
        assert opt.from_spec(json.loads(json.dumps(spec))) == o, name


def test_spec_roundtrip_nondefault_fields():
    o = opt.make("csgd", 0.03, 7, tau0=12.5, decay=0.9, seed=3,
                 quantize="int8")
    assert opt.from_spec(json.loads(json.dumps(opt.to_spec(o)))) == o


def test_every_registered_kind_round_trips_through_spec():
    """Every censor/server kind in the registries survives a spec
    round-trip — and is pinned here by literal kind name, which is what
    the registry-kind-unpinned lint rule checks for (repro.lint)."""
    base = opt.to_spec(opt.make("gd", 0.05, 3))
    assert base["censor"]["kind"] == "never"
    assert base["server"]["kind"] == "gd"
    censor_specs = {
        "never": {"kind": "never"},
        "eq8": {"kind": "eq8", "eps1": 0.2},
        "adaptive": {"kind": "adaptive", "adaptive": 1.5, "decay": 0.9},
        "stochastic": {"kind": "stochastic", "tau0": 10.0, "decay": 0.8,
                       "seed": 0},
    }
    server_specs = {
        "gd": {"kind": "gd", "alpha": 0.05},
        "hb": {"kind": "hb", "alpha": 0.05, "beta": 0.4},
    }
    assert set(censor_specs) == set(opt.registry.CENSOR_KINDS)
    assert set(server_specs) == set(opt.registry.SERVER_KINDS)
    for ckind, cspec in censor_specs.items():
        for skind, sspec in server_specs.items():
            spec = dict(base, censor=cspec, server=sspec)
            rebuilt = opt.from_spec(json.loads(json.dumps(spec)))
            round_trip = opt.to_spec(rebuilt)
            assert round_trip["censor"]["kind"] == ckind
            assert round_trip["server"]["kind"] == skind
            assert opt.from_spec(round_trip) == rebuilt


def test_unknown_algorithm_lists_valid_names():
    with pytest.raises(ValueError) as ei:
        opt.make("no_such_algo", 0.1, 3)
    msg = str(ei.value)
    for name in opt.names():
        assert name in msg
    with pytest.raises(ValueError):
        opt.make_for_point("also_missing", 0.1, 3)


def test_unknown_spec_kind_raises():
    spec = opt.to_spec(opt.make("gd", 0.1, 3))
    spec["censor"] = {"kind": "martian"}
    with pytest.raises(ValueError, match="martian"):
        opt.from_spec(spec)


def test_make_for_point_filters_by_signature():
    """gd's builder takes no beta/eps1/seed; the sweep engine's uniform
    keyword set must not crash it."""
    o = opt.make_for_point("gd", 0.1, 3, beta=0.7, eps1=0.2, quantize=None,
                           seed=4)
    assert isinstance(o.censor, opt.NeverCensor)
    assert o.beta == 0.0


def test_with_hparams_semantics():
    base = opt.make("chb", 0.1, 4)
    o = base.with_hparams(alpha=0.2, beta=0.0, eps1=0.5)
    assert (o.alpha, o.beta, o.eps1) == (0.2, 0.0, 0.5)
    # NeverCensor upgrades to Eq8 when an eps1 axis is swept
    o2 = opt.make("hb", 0.1, 4).with_hparams(eps1=0.5)
    assert isinstance(o2.censor, opt.Eq8Censor)
    # adaptive censors ignore the eps axis (legacy config precedence)
    ad = opt.ComposedOptimizer(
        censor=opt.AdaptiveCensor(0.3), transport=opt.DenseTransport(),
        server=opt.HeavyBall(0.1), num_workers=4)
    assert isinstance(ad.with_hparams(eps1=0.5).censor, opt.AdaptiveCensor)
    # stochastic (and custom) censors own their thresholds: kept as
    # composed, never silently swapped for Eq8 (the spec must stay honest)
    sc = opt.make("csgd", 0.1, 4, tau0=7.0)
    swept = sc.with_hparams(alpha=0.2, beta=0.0, eps1=0.5)
    assert isinstance(swept.censor, opt.StochasticCensor)
    assert swept.censor.tau0 == 7.0
    # a GD server is promoted to HeavyBall when a beta axis is swept
    # (bit-identical at beta=0), so lag/gd bases sweep like legacy configs
    gd_based = opt.make("lag", 0.1, 4)
    hb_swept = gd_based.with_hparams(beta=0.4)
    assert isinstance(hb_swept.server, opt.HeavyBall)
    assert hb_swept.beta == 0.4 and hb_swept.alpha == 0.1


def test_run_sweep_accepts_gd_server_base(linreg):
    """A lag/gd ComposedOptimizer base must sweep (regression: the GD
    server used to raise on the engine's always-present beta axis) and
    stay bit-exact vs per-point runs."""
    from repro.core.censoring import paper_eps1
    a = linreg.alpha_paper
    base = opt.make("lag", a, 5)
    pts = [sweep.GridPoint(alpha=a, beta=0.0, eps1=paper_eps1(a, 5)),
           sweep.GridPoint(alpha=a, beta=0.0, eps1=0.0)]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=60,
                          base_cfg=base)
    for p, hist in zip(pts, res.histories):
        ref = simulator.run(opt.ComposedOptimizer(
            censor=opt.Eq8Censor(p.eps1), transport=opt.DenseTransport(),
            server=opt.HeavyBall(p.alpha, p.beta), num_workers=5),
            linreg.task, 60)
        exactness.assert_bitwise(hist, ref)


def test_run_sweep_keeps_stochastic_base_censor(linreg):
    """base_cfg with a StochasticCensor sweeps alpha without the censor
    being silently replaced — and the recorded spec says so."""
    a = linreg.alpha_paper
    base = opt.make("csgd", a, 5, tau0=1e3, decay=0.99)
    pts = [sweep.GridPoint(alpha=a), sweep.GridPoint(alpha=a * 0.5)]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=40,
                          base_cfg=base)
    for spec in res.specs:
        assert spec["censor"]["kind"] == "stochastic"
        assert spec["censor"]["tau0"] == 1e3
    exactness.assert_bitwise(res.histories[0],
                             simulator.run(base, linreg.task, 40))
    # ...but a VARYING eps axis over such a base would be silently
    # ignored trajectory-wise — run_sweep must refuse it loudly
    bad = [sweep.GridPoint(alpha=a, eps1=0.1),
           sweep.GridPoint(alpha=a, eps1=0.2)]
    with pytest.raises(ValueError, match="eps1 hook"):
        sweep.run_sweep(bad, task=linreg.task, num_iters=5, base_cfg=base)


def test_hyperparameter_views(linreg):
    o = opt.make("chb", 0.05, 9, quantize="int8")
    assert o.alpha == 0.05 and o.beta == 0.4 and o.eps1 > 0
    assert o.quantize == "int8" and o.adaptive == 0.0
    assert o.name == "chb"
    assert opt.make("gd", 0.05, 9).name == "gd"
    assert opt.make("hb", 0.05, 9).name == "hb"
    assert opt.make("lag", 0.05, 9).name == "lag"


# --------------------------------------------------------- csgd end-to-end
def _csgd(alpha, m, tau0=50.0, decay=0.98, seed=0):
    return opt.make("csgd", alpha, m, tau0=tau0, decay=decay, seed=seed)


def test_csgd_simulator_censors_and_progresses(linreg):
    o = _csgd(linreg.alpha_paper, 5, tau0=1e3, decay=0.98)
    hist = simulator.run(o, linreg.task, 600)
    total = int(np.asarray(hist.comm_cum)[-1])
    assert 0 < total < 5 * 600            # censors, but the bank stays live
    assert float(hist.objective[-1]) < float(hist.objective[0])
    fstar = float(simulator.estimate_fstar(linreg.task,
                                           linreg.alpha_paper, 20000))
    # GD-rate convergence under stochastic censoring: solidly past 1% of
    # the initial error (momentum-free, so slower than chb's tail)
    assert float(hist.objective[-1]) - fstar < \
        1e-2 * (float(hist.objective[0]) - fstar)


def test_csgd_fed_sync_anchor_matches_simulator(linreg):
    """Synchronous edge schedule == simulator draw-for-draw: the per-client
    key folding must reproduce the batched censor decisions exactly."""
    o = _csgd(linreg.alpha_paper, 5, tau0=1e3, decay=0.99)
    ref = simulator.run(o, linreg.task, 60)
    hist = fed.run_edge(o, linreg.task, fed.sync_config(5), 60)
    exactness.assert_close_run(hist, ref, "event_runtime")


def test_named_point_defaults_use_builder_defaults(linreg):
    """GridPoint(algo="chb") with beta/eps1 left at the grid's 0.0
    defaults must run the REAL registered chb (paper beta=0.4, Sec.-IV
    eps1) — not an uncensored gd mislabeled chb (regression)."""
    a = linreg.alpha_paper
    pts = [sweep.GridPoint(alpha=a, algo="chb"),
           sweep.GridPoint(alpha=a, beta=0.2, algo="chb")]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=60)
    assert res.num_programs == 2      # set vs unset beta axis differ
    spec0 = res.specs[0]
    assert spec0["censor"]["kind"] == "eq8" and \
        spec0["censor"]["eps1"] > 0           # paper default applied
    assert spec0["server"] == {"kind": "hb", "alpha": float(a), "beta": 0.4}
    exactness.assert_bitwise(
        res.histories[0], simulator.run(opt.make("chb", a, 5), linreg.task,
                                        60))
    # the explicitly-set beta point really used beta=0.2
    assert res.specs[1]["server"]["beta"] == 0.2


def test_csgd_sweep_partition_bit_exact(linreg):
    """GridPoint(algo="csgd") compiles as its own partition and reproduces
    the per-point simulator run bit-exactly (tau0 swept via the eps axis)."""
    a = linreg.alpha_paper
    chb_o = opt.make("chb", a, 5)
    pts = [sweep.GridPoint(alpha=chb_o.alpha, beta=chb_o.beta,
                           eps1=chb_o.eps1),
           sweep.GridPoint(alpha=a, eps1=1e3, algo="csgd"),
           sweep.GridPoint(alpha=a, eps1=50.0, algo="csgd")]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=80)
    assert res.num_programs == 2          # continuum + csgd partition
    assert [p.algo_name for p in res.points] == ["chb", "csgd", "csgd"]
    for p, hist in zip(pts[1:], res.histories[1:]):
        ref = simulator.run(
            opt.make("csgd", p.alpha, 5, tau0=p.eps1), linreg.task, 80)
        exactness.assert_bitwise(hist, ref)


def test_csgd_fed_scenario_sweep_ideal_anchor(linreg):
    """csgd also runs through the synchronous fed-scenario sweep; the
    ideal point reproduces simulator.run exactly."""
    o = _csgd(linreg.alpha_paper, 5, tau0=1e3, decay=0.99)
    grid = sweep.FedScenarioGrid(loss_prob=(0.0, 0.3))
    res = sweep.run_fed_sweep(o, linreg.task, grid, num_rounds=60)
    ref = simulator.run(o, linreg.task, 60)
    i = res.points.index(sweep.FedScenarioPoint(0.0, 1.0, 1.0, 0))
    np.testing.assert_array_equal(res.objective[i],
                                  np.asarray(ref.objective))
    np.testing.assert_array_equal(
        res.transmit_mask[i], np.asarray(ref.mask).astype(np.int8))


# ------------------------------------------- artifact reproducibility
def test_sweep_artifact_specs_rebuild_exact_runs(linreg, tmp_path):
    """--json artifacts carry full registry specs: a run is reproducible
    from the artifact alone, without the code that made it."""
    a = linreg.alpha_paper
    chb_o = opt.make("chb", a, 5)
    pts = [sweep.GridPoint(alpha=chb_o.alpha, beta=chb_o.beta,
                           eps1=chb_o.eps1),
           sweep.GridPoint(alpha=a, eps1=200.0, algo="csgd")]
    res = sweep.run_sweep(pts, task=linreg.task, num_iters=50)
    path = tmp_path / "artifact.json"
    res.to_json(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["specs"]) == 2
    for i, spec in enumerate(doc["specs"]):
        rebuilt = opt.from_spec(spec)
        rerun = simulator.run(rebuilt, linreg.task, 50)
        np.testing.assert_array_equal(np.asarray(rerun.objective),
                                      np.asarray(doc["objective"][i]))
        np.testing.assert_array_equal(np.asarray(rerun.comm_cum),
                                      np.asarray(doc["comm_cum"][i]))
    # the csgd spec names its composition, not just "csgd"
    assert doc["specs"][1]["censor"]["kind"] == "stochastic"
    assert doc["specs"][1]["server"]["kind"] == "gd"


# --------------------------------------------------- protocol boundaries
def test_minimal_protocol_optimizer_runs_in_simulator(linreg):
    """A bare init/step implementation runs through the simulator; the
    stage hosts (fed, fed-sweep) reject it with a clear TypeError instead
    of a raw attribute crash."""
    class Wrapped:
        def __init__(self, inner):
            self.inner = inner
            self.num_workers = inner.num_workers

        def init(self, params):
            return self.inner.init(params)

        def step(self, state, params, grads):
            return self.inner.step(state, params, grads)

    inner = opt.make("chb", linreg.alpha_paper, 5)
    wrapped = Wrapped(inner)
    exactness.assert_bitwise(simulator.run(wrapped, linreg.task, 40),
                             simulator.run(inner, linreg.task, 40))
    with pytest.raises(TypeError, match="ComposedOptimizer"):
        fed.run_edge(wrapped, linreg.task, fed.sync_config(5), 5)
    with pytest.raises(TypeError, match="ComposedOptimizer"):
        sweep.run_fed_sweep(wrapped, linreg.task,
                            sweep.FedScenarioGrid(), 5)


def test_distributed_strategies_reject_unrealizable_censors():
    """The scan/pod training strategies only realize eq-8/uncensored
    policies; a stochastic censor must be refused loudly, not silently
    run uncensored through the flat eps1 view."""
    from repro.core import distributed
    o = opt.make("csgd", 0.05, 4, tau0=10.0)
    with pytest.raises(NotImplementedError, match="StochasticCensor"):
        distributed.make_scan_step(o, lambda p, b: 0.0)
    # eq-8 compositions still build fine
    distributed.make_scan_step(opt.make("chb", 0.05, 4), lambda p, b: 0.0)


def test_sweep_runs_custom_stage_without_spec(linreg):
    """A composition using a censor class outside the spec vocabulary is
    still sweepable — its spec is recorded as None instead of aborting."""
    import dataclasses as dc

    @dc.dataclass(frozen=True)
    class EveryOther:
        supports_event_runtime = True

        def init(self, num_workers):
            return jnp.zeros((), jnp.int32)

        def decide(self, k, delta_sq, step_sq):
            on = (k % 2 == 0).astype(jnp.float32)
            return jnp.full(delta_sq.shape, 1.0) * on, k + 1

        def client_decide(self, round_index, worker, delta_sq, step_sq):
            return (round_index % 2) == 0

    base = opt.ComposedOptimizer(
        censor=EveryOther(), transport=opt.DenseTransport(),
        server=opt.HeavyBall(linreg.alpha_paper, 0.4), num_workers=5)
    res = sweep.run_sweep([sweep.GridPoint(alpha=linreg.alpha_paper)],
                          task=linreg.task, num_iters=20, base_cfg=base)
    assert res.specs == (None,)
    assert int(res.comm_cum[0, -1]) == 5 * 10     # every other round
    o = opt.ComposedOptimizer(
        censor=opt.AdaptiveCensor(0.3), transport=opt.DenseTransport(),
        server=opt.HeavyBall(linreg.alpha_paper, 0.4), num_workers=5)
    with pytest.raises(NotImplementedError, match="[Aa]daptive"):
        fed.run_edge(o, linreg.task, fed.sync_config(5), 5)


def test_unknown_quantize_mode_raises():
    with pytest.raises(ValueError, match="int8"):
        opt.make("chb", 0.1, 4, quantize="int4")


# ------------------------------------------------------ mask properties
def test_censor_mask_monotone_in_eps1_concrete():
    dsq = jnp.asarray([0.5, 1.0, 2.0, 8.0], jnp.float32)
    ssq = jnp.asarray(4.0, jnp.float32)
    prev = None
    for eps1 in (0.0, 0.1, 0.25, 0.5, 2.0, 10.0):
        mask, _ = opt.Eq8Censor(eps1).decide((), dsq, ssq)
        m = np.asarray(mask)
        if prev is not None:
            assert (m <= prev).all(), eps1
        prev = m


try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(dsq=st.lists(st.floats(0.0, 1e6), min_size=1, max_size=8),
           ssq=st.floats(0.0, 1e6),
           e1=st.floats(0.0, 1e3), e2=st.floats(0.0, 1e3))
    # a subnormal threshold, which XLA flushes to zero when it is traced
    @example(dsq=[0.0], ssq=0.0, e1=0.0, e2=5e-324)
    # a delta just above the threshold: both branches must compute it alike
    @example(dsq=[1.005], ssq=1.0, e1=0.0, e2=1.0)
    def test_property_censor_mask_monotone_in_eps1(dsq, ssq, e1, e2):
        """Raising eps1 can only censor MORE workers (eq. 8 is a one-sided
        threshold), for static and traced thresholds alike."""
        lo, hi = sorted((e1, e2))
        d = jnp.asarray(dsq, jnp.float32)
        s = jnp.asarray(ssq, jnp.float32)
        m_lo, _ = opt.Eq8Censor(lo).decide((), d, s)
        m_hi, _ = opt.Eq8Censor(hi).decide((), d, s)
        assert (np.asarray(m_hi) <= np.asarray(m_lo)).all()
        # traced threshold decides identically (sweep bit-exactness)
        m_tr = jax.jit(lambda e: opt.Eq8Censor(e).decide((), d, s)[0])(
            jnp.float64(hi))
        np.testing.assert_array_equal(np.asarray(m_tr), np.asarray(m_hi))

    @settings(max_examples=25, deadline=None)
    @given(k=st.integers(0, 500), seed=st.integers(0, 100))
    def test_property_stochastic_censor_tau_decays(k, seed):
        """The CSGD threshold sequence decays geometrically, so any fixed
        delta's transmit probability is non-decreasing in k."""
        pol = opt.StochasticCensor(tau0=100.0, decay=0.97, seed=seed)
        t0 = float(pol._tau(jnp.asarray(k)))
        t1 = float(pol._tau(jnp.asarray(k + 1)))
        assert t1 <= t0

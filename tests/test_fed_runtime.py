"""Event-driven edge runtime (repro.fed): sync-mode equivalence anchor,
stale-bank semantics under loss/stragglers, and energy/latency accounting."""
import dataclasses

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import fed
from repro.core import baselines, simulator
from repro.core.quantize import payload_bytes_dense
from repro.data import paper_tasks


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=5, n_per=30, d=20, seed=0)


# ------------------------------------------------- sync-mode correctness anchor
@pytest.mark.parametrize("algo", ["gd", "hb", "lag", "chb"])
def test_sync_mode_reproduces_simulator(linreg, algo):
    """Zero latency + lossless + full participation + full quorum must be
    numerically identical to core/simulator.run — objective AND cumulative
    uplink trajectories."""
    cfg = baselines.ALGORITHMS[algo](linreg.alpha_paper, 5)
    ref = simulator.run(cfg, linreg.task, 60)
    hist = fed.run_edge(cfg, linreg.task, fed.sync_config(5), 60)
    exactness.assert_close_run(hist, ref, "event_runtime")


def test_sync_mode_reproduces_simulator_int8(linreg):
    """The quantized (per-worker-scale) path is part of the anchor too."""
    cfg = dataclasses.replace(baselines.chb(linreg.alpha_paper, 5),
                              quantize="int8")
    ref = simulator.run(cfg, linreg.task, 60)
    hist = fed.run_edge(cfg, linreg.task, fed.sync_config(5), 60)
    exactness.assert_close_run(hist, ref, "event_runtime")


def test_sync_mode_nn_task():
    """Anchor holds on the paper's nonconvex pytree-parameter task."""
    b = paper_tasks.make_neural_network(m=4, n_per=40, d=8, hidden=6)
    cfg = baselines.chb(0.02, 4)
    ref = simulator.run(cfg, b.task, 25)
    hist = fed.run_edge(cfg, b.task, fed.sync_config(4), 25)
    exactness.assert_close_run(hist, ref, "event_runtime_pytree")


# ----------------------------------------------------------- channel semantics
def test_dropped_uplinks_leave_bank_untouched(linreg):
    """With ~certain loss, no delta ever folds: the stale bank stays zero,
    GD makes no progress, yet air time and energy are still charged."""
    edge = fed.EdgeConfig(
        population=fed.uniform_population(5),
        channel=fed.ChannelConfig(kind="bernoulli", loss_prob=0.999999,
                                  uplink_rate_bps=1e6),
        seed=0)
    cfg = baselines.gd(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 8)
    bank_norm = sum(float(jnp.abs(x).sum())
                    for x in jax.tree_util.tree_leaves(hist.final_bank))
    assert bank_norm == 0.0
    assert np.allclose(hist.objective, hist.objective[0])
    assert hist.mask.sum() == 0
    d = hist.stats.as_dict()
    assert d["dropped"] == d["uplinks"] > 0
    assert d["energy_j"] > 0 and d["tx_s"] > 0


def test_moderate_loss_still_converges(linreg):
    """Bernoulli loss slows but does not break CHB (bank stays consistent)."""
    edge = fed.EdgeConfig(population=fed.uniform_population(5),
                          channel=fed.ChannelConfig.lossy(0.3),
                          seed=1)
    cfg = baselines.chb(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 200)
    fstar = float(simulator.estimate_fstar(linreg.task, linreg.alpha_paper,
                                           20000))
    assert hist.objective[-1] - fstar < 1e-6 * (hist.objective[0] - fstar)
    d = hist.stats.as_dict()
    assert d["dropped"] > 0 and d["delivered"] > 0


def test_channel_models():
    rng = np.random.default_rng(0)
    ch = fed.ChannelConfig(uplink_rate_bps=1e6, overhead_s=0.01)
    tx = ch.uplink(125_000, rng)      # 1 Mbit at 1 Mbps
    assert tx.delivered and tx.time_s == pytest.approx(1.01)
    assert ch.downlink_time(0) == pytest.approx(0.01)
    lossy = fed.ChannelConfig.lossy(0.5)
    outcomes = [lossy.uplink(100, rng).delivered for _ in range(400)]
    assert 0.3 < np.mean(outcomes) < 0.7
    fading = fed.ChannelConfig.fading(uplink_rate_bps=1e6, fading_floor=0.1)
    rates = [fading.uplink(1000, rng).rate_bps for _ in range(200)]
    assert min(rates) >= 0.1 * 1e6 and np.std(rates) > 0
    with pytest.raises(ValueError):
        fed.ChannelConfig(kind="quantum")


# -------------------------------------------------- stragglers / participation
def test_straggler_quorum_folds_stale_arrivals(linreg):
    """quorum<1 advances past stragglers; their late uplinks still fold
    (eq. (5) bank semantics) and are counted as stale folds."""
    pop = fed.straggler_population(5, compute_mean_s=1.0, straggler_frac=0.2,
                                   straggler_slowdown=25.0, jitter="fixed",
                                   seed=0)
    edge = fed.EdgeConfig(population=pop, channel=fed.ChannelConfig(),
                          quorum=0.8, seed=2)
    cfg = baselines.chb(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 120)
    assert hist.stats.as_dict()["stale_folds"] > 0
    # the slow client still contributed uplinks eventually
    slow = int(np.argmax([p.compute_mean_s for p in pop.profiles]))
    assert hist.stats.uplink_count[slow] > 0
    # quorum=0.8 must finish the same rounds in less wall-clock than waiting
    # for the 25x straggler every round
    full = fed.run_edge(cfg, linreg.task,
                        dataclasses.replace(edge, quorum=1.0), 120)
    assert hist.wall_clock[-1] < full.wall_clock[-1]


def test_partial_participation_caps_cohort(linreg):
    edge = fed.EdgeConfig(
        population=fed.uniform_population(5, participation=0.4),
        channel=fed.ChannelConfig.ideal(), seed=3)
    cfg = baselines.chb(0.5 * linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 300)
    per_round = hist.mask.sum(axis=1)
    assert per_round.max() <= 2          # ceil(0.4 * 5)
    fstar = float(simulator.estimate_fstar(linreg.task, linreg.alpha_paper,
                                           20000))
    assert hist.objective[-1] - fstar < 1e-4 * (hist.objective[0] - fstar)


def test_intermittent_availability_makes_progress(linreg):
    edge = fed.EdgeConfig(
        population=fed.intermittent_population(5, avail_p=0.5,
                                               compute_mean_s=0.5),
        seed=4)
    cfg = baselines.chb(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 120)
    assert hist.objective[-1] < hist.objective[0]
    assert hist.stats.total_uplinks < 5 * 120   # not everyone every round


# ------------------------------------------------------------------ accounting
def test_energy_accounting_consistency(linreg):
    em = fed.EnergyModel(uplink_j_per_byte=1e-6, uplink_j_per_tx=1e-3,
                         downlink_j_per_byte=0.0)
    edge = fed.EdgeConfig(
        population=fed.uniform_population(5, compute_mean_s=2.0,
                                          compute_w=3.0),
        channel=fed.ChannelConfig(uplink_rate_bps=1e6),
        energy=em, seed=5)
    cfg = baselines.chb(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, edge, 50)
    d = hist.stats.as_dict()
    expect = (d["uplink_bytes"] * 1e-6 + d["uplinks"] * 1e-3
              + d["compute_s"] * 3.0)
    assert d["energy_j"] == pytest.approx(expect, rel=1e-9)
    # exact byte count: every transmission carries the full dense payload
    assert d["uplink_bytes"] == d["uplinks"] * \
        payload_bytes_dense(linreg.task.init_params)
    # wall clock covers at least one compute phase per round
    assert hist.wall_clock[-1] >= 50 * 2.0


def test_edge_metrics_to_accuracy(linreg):
    cfg = baselines.chb(linreg.alpha_paper, 5)
    hist = fed.run_edge(cfg, linreg.task, fed.sync_config(5), 200)
    fstar = float(simulator.estimate_fstar(linreg.task, linreg.alpha_paper,
                                           20000))
    met = fed.edge_metrics_to_accuracy(hist, fstar, 1e-6)
    assert met["rounds"] > 0
    assert met["uplinks"] == int(hist.comm_cum[met["rounds"]])
    unreachable = fed.edge_metrics_to_accuracy(hist, fstar, -1.0)
    assert unreachable["rounds"] == -1 and unreachable["uplinks"] == -1


# -------------------------------------------------------------- config guards
def test_rejects_unsupported_modes(linreg):
    edge = fed.sync_config(5)
    bad_gran = dataclasses.replace(baselines.chb(0.1, 5),
                                   granularity="per_tensor")
    with pytest.raises(NotImplementedError):
        fed.run_edge(bad_gran, linreg.task, edge, 2)
    bad_workers = baselines.chb(0.1, 7)
    with pytest.raises(ValueError):
        fed.run_edge(bad_workers, linreg.task, edge, 2)
    with pytest.raises(ValueError):
        fed.EdgeConfig(population=fed.uniform_population(5), quorum=0.0)
    with pytest.raises(ValueError):
        fed.uniform_population(5, participation=1.5)

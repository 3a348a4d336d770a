"""repro.obs: in-graph telemetry + host-side sinks.

Pins the observability contracts:

  * **bit-exactness**: metrics-on runs are bit-identical to metrics-off
    runs on both backends, and reproduce the same pin as
    tests/test_backend.py;
  * **zero extra compiles**: collecting metrics through ``run_sweep``
    neither changes the partition keys nor adds compiled programs or
    kernel retraces (pinned via ``obs.compile_log``);
  * **exact byte accounting**: the split-int32 ``CommStats`` counters —
    and the MetricBag entries derived from them — stay exact past
    float32's 2^24 integer limit;
  * the JSONL ``RunLog`` event schema, the ``obs.bench`` artifact schema
    (+ CLI validator + ``tools/bench_diff.py``), the stage ``metrics``
    hooks, the fed runtime's staleness histogram, and the
    ``obs.hlo_report`` trip-count-weighted analysis.
"""
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
from repro import fed, obs, opt, sweep
from repro.core import simulator
from repro.core.accounting import MIB, CommStats
from repro.data import paper_tasks
from repro.kernels import ops as kernel_ops
from repro.obs import bench, compile_log

M = 5
ITERS = 60

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=M, n_per=30, d=20, seed=0)


def _as_f32(task):
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
    return task._replace(init_params=cast(task.init_params),
                         worker_data=cast(task.worker_data))


@pytest.fixture(scope="module")
def task32(linreg):
    return _as_f32(linreg.task)


# ===================================================== bit-exactness anchor
@pytest.mark.parametrize("backend", opt.BACKENDS)
def test_metrics_on_matches_golden_fingerprint(linreg, task32, backend):
    """Metrics ride alongside the state: the pinned run (the same as
    tests/test_backend.py's, chb at f32 for 60 iterations) is unchanged
    with collection on, on both backends."""
    o = opt.make("chb", linreg.alpha_paper, M, backend=backend)
    h = simulator.run(o, task32, ITERS, collect_metrics=True)
    exactness.assert_matches_pin(h, exactness.PINS["f32/dense"])
    # and the bag itself came back as stacked (K,) series
    assert h.metrics and all(np.asarray(v).shape == (ITERS,)
                             for v in h.metrics.values())


def test_metrics_off_by_default(linreg, task32):
    h = simulator.run(opt.make("chb", linreg.alpha_paper, M), task32, 10)
    assert h.metrics == ()


def test_metrics_bit_identity_all_fields(linreg):
    """Every History field (params and bank included) is bit-identical
    between metrics-on and metrics-off f64 runs."""
    o = opt.make("chb", linreg.alpha_paper, M, quantize="int8")
    h0 = simulator.run(o, linreg.task, ITERS)
    h1 = simulator.run(o, linreg.task, ITERS, collect_metrics=True)
    exactness.assert_bitwise(h0, h1)


# =============================================== MetricBag content + hooks
def test_base_bag_contents(linreg, task32):
    o = opt.make("chb", linreg.alpha_paper, M)
    h = simulator.run(o, task32, ITERS, collect_metrics=True)
    bag = h.metrics
    # censor rate is 1 - mean(mask) per round
    np.testing.assert_allclose(
        np.asarray(bag["censor_rate"]),
        1.0 - np.asarray(h.mask).mean(axis=1), atol=1e-6)
    # cumulative uplink count matches the comm trajectory
    np.testing.assert_array_equal(
        np.asarray(bag["comm/uplink_total"]).astype(np.int64),
        np.asarray(h.comm_cum))
    # final-round bag bytes == the exact split counters
    assert float(np.asarray(bag["comm/uplink_bytes"])[-1]) == float(
        h.final_state.comm.uplink_bytes_exact())
    # eq-8 censor reports its (traced) threshold by registry kind
    assert "censor/eq8/eps1" in bag
    assert "server/hb/alpha" in bag and "server/hb/beta" in bag


def test_stage_hooks_namespaced_by_kind(linreg, task32):
    # int8 transport adds the EF-residual norm under transport/int8/
    o = opt.make("chb", linreg.alpha_paper, M, quantize="int8")
    h = simulator.run(o, task32, 30, collect_metrics=True)
    assert "transport/int8/ef_residual_sqnorm" in h.metrics
    # stochastic censor reports its decaying threshold
    o2 = opt.make("csgd", linreg.alpha_paper, M, tau0=5.0)
    h2 = simulator.run(o2, task32, 30, collect_metrics=True)
    tau = np.asarray(h2.metrics["censor/stochastic/tau"])
    assert tau.shape == (30,) and tau[0] > tau[-1] > 0
    # adaptive censor reports its EMA state
    o3 = opt.ComposedOptimizer(
        censor=opt.AdaptiveCensor(adaptive=1.0),
        transport=opt.DenseTransport(),
        server=opt.HeavyBall(linreg.alpha_paper, 0.4), num_workers=M)
    h3 = simulator.run(o3, task32, 30, collect_metrics=True)
    assert "censor/adaptive/ema_mean" in h3.metrics


def test_metric_names_without_running(linreg, task32):
    o = opt.make("chb", linreg.alpha_paper, M)
    names = obs.metric_names(o, task32.init_params)
    assert "censor_rate" in names and "censor/eq8/eps1" in names
    # eval_shape must not have compiled or executed anything kernel-side
    h = simulator.run(o, task32, 5, collect_metrics=True)
    assert names == tuple(sorted(h.metrics))


def test_summarize_reducers():
    series = {"a": np.arange(5.0), "b": np.ones(5)}
    assert obs.summarize(series) == {"a": 4.0, "b": 1.0}
    assert obs.summarize(series, reducer=np.mean)["a"] == 2.0


# ============================================ exact byte accounting > 2^24
def test_commstats_exact_past_2pow24():
    """The split-int32 counters register every byte far past float32's
    2^24 integer limit, and the MetricBag view agrees exactly (f64)."""
    stats = CommStats.init(4)
    payload = 3 * MIB + 17          # odd size: exercises the carry
    mask = jnp.ones((4,), jnp.float32)
    update = jax.jit(lambda s: s.update(mask, payload))
    rounds = 2000                   # 4 workers * 2000 * ~3MiB ≈ 25 GiB
    for _ in range(rounds):
        stats = update(stats)
    exact = stats.uplink_bytes_exact()
    assert exact == 4 * rounds * payload
    assert exact > (1 << 24)        # past the f32 integer floor
    assert 0 <= int(stats.uplink_rem) < MIB
    # a single f32 accumulator would have lost the +17 increments
    f32_acc = np.float32(0)
    for _ in range(rounds):
        f32_acc = np.float32(f32_acc + np.float32(4 * payload))
    assert int(f32_acc) != exact
    # the metrics() view (f64 under x64) reproduces the exact count
    assert float(stats.metrics()["comm/uplink_bytes"]) == float(exact)


def test_commstats_metrics_keys():
    stats = CommStats.init(3)
    bag = stats.metrics()
    assert set(bag) == {"comm/uplink_total", "comm/uplink_bytes",
                        "comm/downlink_count", "comm/iterations"}


# =============================== sweep round-trip: no retraces, same keys
def test_sweep_metrics_roundtrip_zero_extra_compiles(linreg, task32):
    """collect_metrics must not change partition keys, add compiled
    programs, or retrace any kernel dispatch."""
    grid = sweep.ConfigGrid(
        alpha=[0.5 * linreg.alpha_paper, linreg.alpha_paper],
        beta=[0.0, 0.4], eps1=[0.5, 2.0])
    base = opt.make("chb", linreg.alpha_paper, M, backend="pallas")

    with compile_log.track() as off:
        res0 = sweep.run_sweep(grid, task32, num_iters=40, base_cfg=base)
    with compile_log.track() as on:
        res1 = sweep.run_sweep(grid, task32, num_iters=40, base_cfg=base,
                               collect_metrics=True)
    # identical partitioning and identical compile/trace activity
    assert res1.num_programs == res0.num_programs == 1
    assert on.counts == off.counts
    assert on.counts.get("sweep/partition") == 1
    assert on.counts.get("kernels/tree_delta_sqnorms") == 1
    # trajectories bit-identical, metrics only on the collecting run
    for i in range(len(res0)):
        exactness.assert_bitwise(res0.history(i), res1.history(i))
        assert res0.metrics(i) == {}
        bag = res1.metrics(i)
        assert np.asarray(bag["censor_rate"]).shape == (40,)
        # the traced hyperparameters round-trip through the bag
        assert float(np.asarray(bag["censor/eq8/eps1"])[-1]) == \
            pytest.approx(res1.points[i].eps1)
    # summary rows are JSON-ready floats
    summary = res1.metrics_summary()
    assert len(summary) == len(res1)
    json.dumps(summary)
    # and to_json embeds them only when collected
    assert "metrics" in json.loads(res1.to_json(include_trajectories=False))
    assert "metrics" not in json.loads(
        res0.to_json(include_trajectories=False))


# ====================================================== compile_log itself
def test_compile_log_namespaces_and_track():
    compile_log.reset("t-ns")
    ns = compile_log.namespace("t-ns")
    compile_log.record("t-ns", "x")
    compile_log.record("t-ns", "x")
    assert ns == {"x": 2}               # live dict view
    with compile_log.track() as tc:
        compile_log.record("t-ns", "y")
    assert tc.counts == {"t-ns/y": 1}   # delta only
    assert tc.total("t-ns") == 1
    assert compile_log.snapshot()["t-ns/x"] == 2
    compile_log.reset("t-ns")
    assert ns == {}


def test_kernel_trace_counts_is_compile_log_view():
    kernel_ops.reset_trace_counts()
    assert kernel_ops.trace_counts == {}
    assert kernel_ops.trace_counts is compile_log.namespace("kernels")


# ================================================================= RunLog
def test_runlog_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    spec = {"algo": "chb"}
    with obs.RunLog(path, run="t", backend="reference", spec=spec) as log:
        log.write_round(0, {"censor_rate": jnp.float32(0.25)})
        log.write_point(3, {"final_err": 1e-6}, spec={"algo": "gd"},
                        note="tagged")
    events = obs.read_jsonl(path)
    assert [e["event"] for e in events] == ["round", "point"]
    for e in events:
        assert e["schema_version"] == obs.EVENT_SCHEMA_VERSION
        assert e["run"] == "t" and e["backend"] == "reference"
    assert events[0]["metrics"]["censor_rate"] == pytest.approx(0.25)
    assert events[0]["spec"] == spec          # default spec stamped
    assert events[1]["spec"] == {"algo": "gd"}  # per-event override
    assert events[1]["note"] == "tagged"
    # appending reopens cleanly
    with obs.RunLog(path, run="t2") as log:
        log.write("done")
    assert len(obs.read_jsonl(path)) == 3


def test_runlog_in_memory():
    log = obs.RunLog(run="mem")
    log.write_round(0, {"x": np.float64(1.5)})
    assert json.loads(log.lines[0])["metrics"]["x"] == 1.5


# ========================================================== fed runtime
def test_fed_metrics_and_staleness(linreg):
    edge = fed.sync_config(M, seed=0)
    o = opt.make("chb", linreg.alpha_paper, M)
    log = obs.RunLog(run="edge", backend="reference")
    h0 = fed.run_edge(o, linreg.task, edge, 25)
    h1 = fed.run_edge(o, linreg.task, edge, 25, collect_metrics=True,
                      runlog=log)
    assert h0.metrics == ()
    # metrics are observation only: trajectories unchanged
    exactness.assert_bitwise(h0, h1)
    bag = h1.metrics
    assert np.asarray(bag["censor_rate"]).shape == (25,)
    # sync anchor: nothing is ever late or dropped
    assert np.asarray(bag["staleness/h1"]).sum() == 0
    assert np.asarray(bag["staleness/h4p"]).sum() == 0
    assert np.asarray(bag["drops"]).sum() == 0
    # every fold this round arrived fresh
    np.testing.assert_array_equal(np.asarray(bag["staleness/h0"]),
                                  np.asarray(h1.mask).sum(axis=1))
    np.testing.assert_array_equal(np.asarray(bag["comm/uplink_total"]),
                                  np.asarray(h1.comm_cum).astype(np.float64))
    # one JSONL round event per server round
    assert len(log.lines) == 25
    ev = json.loads(log.lines[0])
    assert ev["event"] == "round" and ev["cohort_size"] == M
    # the fed closures trace a bounded number of times (client_eval sees
    # two ssq signatures: the round-0 literal and the traced update), and
    # the count must NOT grow with the number of rounds
    with compile_log.track() as t5:
        fed.run_edge(o, linreg.task, edge, 5)
    with compile_log.track() as t12:
        fed.run_edge(o, linreg.task, edge, 12)
    assert t5.counts == t12.counts
    assert t5.counts.get("fed/server_update") == 1
    assert t5.counts.get("fed/client_eval", 0) <= 2


def test_fed_staleness_buckets_with_stragglers(linreg):
    """A straggler cohort with partial quorum produces late folds that
    land in the >=1-round staleness buckets."""
    edge = fed.EdgeConfig(
        population=fed.straggler_population(
            M, compute_mean_s=1.0, straggler_frac=0.4,
            straggler_slowdown=25.0, jitter="exp", seed=3),
        channel=fed.ChannelConfig(uplink_rate_bps=1e6),
        quorum=3.0 / 5.0, seed=3)
    o = opt.make("hb", linreg.alpha_paper * 0.5, M)
    h = fed.run_edge(o, linreg.task, edge, 40, collect_metrics=True)
    late = (np.asarray(h.metrics["staleness/h1"]).sum()
            + np.asarray(h.metrics["staleness/h2_3"]).sum()
            + np.asarray(h.metrics["staleness/h4p"]).sum())
    assert late > 0
    assert late == int(h.stats.stale_count.sum())
    # every folded delta landed in exactly one bucket
    assert np.asarray(h.metrics["staleness/h0"]).sum() + late == \
        np.asarray(h.mask).sum()


# ======================================================== bench artifacts
def _tiny_artifact(name="t", us=10.0, mbytes=100.0, traces=None):
    return bench.make_artifact(name, {
        "k": {"row": f"k,{us:.1f},d=1", "seconds": 0.1,
              "backend": ["reference", "pallas"],
              "specs": {"reference": {"algo": "chb"}},
              "measured_bytes": {"reference": mbytes},
              "analytic_bytes": {"reference": 90.0},
              "measured": {"pallas": {"kernel_traces": traces or
                                      {"tree_hb_update": 1}}}}},
        registry=["chb"])


def test_bench_artifact_schema_roundtrip(tmp_path):
    doc = _tiny_artifact()
    assert doc["schema_version"] == bench.SCHEMA_VERSION
    assert doc["kind"] == bench.KIND
    assert set(doc["env"]) == {"jax_version", "backend", "x64"}
    p = str(tmp_path / "BENCH_t.json")
    bench.write_artifact(doc, p)
    assert bench.load_artifact(p) == doc


@pytest.mark.parametrize("mutate,msg", [
    (lambda d: d.pop("schema_version"), "schema_version"),
    (lambda d: d.update(kind="other"), "kind"),
    (lambda d: d.update(env=None), "env"),
    (lambda d: d["benchmarks"]["k"].pop("row"), "row"),
    (lambda d: d["benchmarks"]["k"].update(specs=3), "specs"),
    (lambda d: d["benchmarks"]["k"].update(measured_bytes=[1]),
     "measured_bytes"),
])
def test_bench_validation_catches(mutate, msg):
    doc = _tiny_artifact()
    mutate(doc)
    errs = bench.validate_artifact(doc)
    assert errs and any(msg in e for e in errs), errs


def test_bench_validate_cli(tmp_path):
    good = str(tmp_path / "good.json")
    bench.write_artifact(_tiny_artifact(), good)
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"schema_version": 1}, f)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    ok = subprocess.run(
        [sys.executable, "-m", "repro.obs.bench", "--validate", good],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    fail = subprocess.run(
        [sys.executable, "-m", "repro.obs.bench", "--validate", bad],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert fail.returncode == 1
    assert "kind" in fail.stdout


def test_bench_diff_cli(tmp_path):
    old = str(tmp_path / "old.json")
    new_ok = str(tmp_path / "new_ok.json")
    new_bad = str(tmp_path / "new_bad.json")
    bench.write_artifact(_tiny_artifact(us=10.0), old)
    bench.write_artifact(_tiny_artifact(us=11.0), new_ok)
    bench.write_artifact(
        _tiny_artifact(us=50.0, mbytes=500.0,
                       traces={"tree_hb_update": 4}), new_bad)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    script = os.path.join(REPO, "tools", "bench_diff.py")
    ok = subprocess.run([sys.executable, script, old, new_ok],
                        capture_output=True, text=True, env=env, cwd=REPO)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "no regressions" in ok.stdout
    bad = subprocess.run([sys.executable, script, old, new_bad],
                         capture_output=True, text=True, env=env, cwd=REPO)
    assert bad.returncode == 1
    assert "us_per_call" in bad.stdout
    assert "measured_bytes" in bad.stdout
    assert "retrace" in bad.stdout


def test_checked_in_artifacts_validate():
    """The committed BENCH_*.json files at the repo root stay schema-valid."""
    import glob
    paths = glob.glob(os.path.join(REPO, "BENCH_*.json"))
    assert paths, "no BENCH_*.json artifacts checked in at the repo root"
    for p in paths:
        doc = bench.load_artifact(p)       # raises on violation
        assert doc["benchmarks"], p


# ========================================================== profiler hooks
def test_annotate_and_named_scope_run(linreg, task32):
    with obs.annotate("test/span"):
        x = jnp.ones(3) + 1
    assert float(x.sum()) == 6.0
    # named_scope shows up in the composed step's HLO metadata
    o = opt.make("chb", linreg.alpha_paper, M)
    state = o.init(task32.init_params)
    grads = jax.vmap(task32.grad_fn, in_axes=(None, 0))(
        task32.init_params, task32.worker_data)
    hlo = jax.jit(lambda s, p, g: o.step(s, p, g)).lower(
        state, task32.init_params, grads).compile().as_text()
    assert "chb_step[reference]" in hlo


# ============================================================= hlo_report
def test_hlo_report_scan_trip_counts(task32, linreg):
    """The report weights scan-body ops by trip count; XLA's own
    cost_analysis is also exposed for the measured-bytes artifacts."""
    from repro.obs import hlo_report
    o = opt.make("chb", linreg.alpha_paper, M)
    fn = lambda p: simulator.trajectory(  # noqa: E731
        o, task32._replace(init_params=p), 50).objective
    text = hlo_report.compiled_text(fn, task32.init_params)
    rep = hlo_report.report(text, top=5)
    assert len(rep["hbm_ops"]) == 5
    # something in the module runs 50x (the scan body's ops)
    assert max(r["mult"] for r in rep["hbm_ops"]) >= 50
    assert rep["totals"]["hbm_bytes"] > 0
    out = hlo_report.format_report(rep)
    assert "top HBM ops" in out
    cost = hlo_report.cost_summary(fn, task32.init_params)
    assert cost["bytes_accessed"] > 0

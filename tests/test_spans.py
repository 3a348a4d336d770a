"""The host spans of ``fed.run_mesh`` and ``train.trainer.train``.

Each entry point runs once under a CPU profiler session. The spans are read
back from the trace: their names, how they nest, one span per round or step
numbered in order, the sync's count of arrays read back to the host in its
one fetch, a log span only at logging steps. A traced run must compute what
an untraced one does.

The profiler is one per process, so every capture lives in this file.
"""
import glob
import json
import os
import subprocess
import sys
import tempfile
import textwrap
from typing import NamedTuple

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import exactness
from repro import opt
from repro.configs import get
from repro.data import paper_tasks
from repro.fed.mesh import MeshScenario, run_mesh
from repro.train.trainer import TrainConfig, train

M, ROUNDS = 5, 3
TESTS = os.path.dirname(os.path.abspath(__file__))
MESH_SPANS = {"run_mesh", "run_mesh/setup", "run_mesh/round",
              "run_mesh/dispatch", "run_mesh/sync", "run_mesh/account",
              "run_mesh/history"}
TRAIN_SPANS = {"train", "train/setup", "train/step", "train/data",
               "train/dispatch", "train/log"}


class Span(NamedTuple):
    name: str
    start: int
    end: int
    stats: dict

    def holds(self, other) -> bool:
        return self.start <= other.start and other.end <= self.end


def _traced(fn):
    """``fn()`` under a profiler session: its result and the program's host
    spans, in order of start."""
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        spans = [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      {k: v for k, v in ev.stats})
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host")
                 for line in plane.lines for ev in line.events
                 if ev.name in MESH_SPANS | TRAIN_SPANS]
    return out, sorted(spans, key=lambda s: s.start)


def _named(spans, name):
    return [s for s in spans if s.name == name]


# ---------------------------------------------------------------- run_mesh
@pytest.fixture(scope="module")
def mesh_run():
    bundle = paper_tasks.make_linear_regression(m=M, n_per=30, d=20, seed=0)
    o = opt.make("chb", bundle.alpha_paper, M)
    scenario = MeshScenario(participation=0.7, loss_prob=0.3, quorum=0.6,
                            seed=5)

    def call(**kw):
        return run_mesh(o, bundle.task, ROUNDS, scenario=scenario, **kw)
    return call


@pytest.fixture(scope="module")
def mesh_traced(mesh_run):
    return _traced(mesh_run)


def test_run_mesh_spans_nest_by_phase(mesh_traced):
    """Round k's span dispatches round k, then reads and accounts for
    round k-1; the last round's span also drains its own."""
    _, spans = mesh_traced
    assert {s.name for s in spans} == MESH_SPANS
    call, = _named(spans, "run_mesh")
    assert all(call.holds(s) for s in spans)
    setup, = _named(spans, "run_mesh/setup")
    history, = _named(spans, "run_mesh/history")
    rounds = _named(spans, "run_mesh/round")
    assert setup.end <= rounds[0].start
    assert rounds[-1].end <= history.start
    for k, r in enumerate(rounds):
        inner = [s for s in spans if r.holds(s) and s is not r]
        reads = [k - 1] * (k > 0) + [k] * (k == ROUNDS - 1)
        assert [s.name for s in inner] == ["run_mesh/dispatch"] + [
            "run_mesh/sync", "run_mesh/account"] * len(reads)
        assert [s.stats["round"] for s in inner
                if s.name == "run_mesh/sync"] == reads
        assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))


def test_run_mesh_one_round_span_per_round(mesh_traced):
    _, spans = mesh_traced
    rounds = _named(spans, "run_mesh/round")
    assert [r.stats["step_num"] for r in rounds] == list(range(ROUNDS))
    assert all(a.end <= b.start for a, b in zip(rounds, rounds[1:]))
    for name in ("run_mesh/dispatch", "run_mesh/sync", "run_mesh/account"):
        assert len(_named(spans, name)) == ROUNDS, name
    assert [s.stats["round"] for s in _named(spans, "run_mesh/sync")] == \
        list(range(ROUNDS))


def _assert_one_fetch(syncs):
    """Each sync blocks on one batched fetch, and says whether the round
    it read was still running as it opened."""
    assert [s.stats["fetches"] for s in syncs] == [1] * ROUNDS
    assert all(s.stats["ahead"] in (0, 1) for s in syncs)


@pytest.mark.parametrize("mask,metrics", [(True, False), (False, False),
                                          (True, True)],
                         ids=["mask", "no-mask", "mask+metrics"])
def test_run_mesh_sync_counts_its_reads(mesh_run, mask, metrics):
    hist, spans = _traced(lambda: mesh_run(collect_mask=mask,
                                           collect_metrics=metrics))
    bag = len(hist.metrics[0]) if metrics else 0    # the shard bag's keys
    # 7 server scalars, then per shard (K = 1) its slowest compute time,
    # its mask rows and each entry of its metric bag, in one fetch
    want = 7 + 1 + mask + bag
    syncs = _named(spans, "run_mesh/sync")
    assert [s.stats["reads"] for s in syncs] == [want] * ROUNDS
    _assert_one_fetch(syncs)


@pytest.mark.parametrize("shards", [2, 4])
def test_run_mesh_sync_reads_two_arrays_per_shard(shards):
    """7 + 2K arrays in one fetch at K > 1, in a child process that sees K
    CPU devices (this one keeps its single-device view)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        from test_spans import _named, _traced
        from repro import opt
        from repro.data import paper_tasks
        from repro.fed.mesh import run_mesh
        from repro.launch.mesh import make_client_mesh
        b = paper_tasks.make_linear_regression(m=8, n_per=30, d=20, seed=0)
        o = opt.make("chb", b.alpha_paper, 8)
        _, spans = _traced(lambda: run_mesh(
            o, b.task, {ROUNDS}, mesh=make_client_mesh({shards})))
        print(json.dumps([s.stats
                          for s in _named(spans, "run_mesh/sync")]))
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(TESTS, "..", "src"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={shards}"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    syncs = [Span("run_mesh/sync", 0, 0, st)
             for st in json.loads(r.stdout.splitlines()[-1])]
    assert [s.stats["reads"] for s in syncs] == [7 + 2 * shards] * ROUNDS
    _assert_one_fetch(syncs)


@pytest.mark.parametrize("route,want", [
    ({"backend": "pallas"}, 2), ({}, 0),
    ({"backend": "pallas", "quantize": "int8"}, 0)],
    ids=["pallas-dense", "reference", "pallas-int8"])
def test_run_mesh_setup_counts_tiled_leaves(route, want):
    """The set-up span says how many bank leaves each shard holds as
    kernel tiles: both of a W-and-b task's on the pallas dense route,
    none where the bank stays untiled."""
    from test_bank_tiles import mlr_task
    task = mlr_task(m=4)
    o = opt.make("chb", 0.5, 4, **route)
    _, spans = _traced(lambda: run_mesh(o, task, 1))
    setup, = _named(spans, "run_mesh/setup")
    assert setup.stats["tiled_leaves"] == want


@pytest.mark.parametrize("draws,want", [
    ({}, 0), ({"participation": 0.7, "loss_prob": 0.3}, 0),
    ({"participation": 0.05, "loss_prob": 0.2, "quorum": 0.8}, 32)],
    ids=["sync", "full", "gathered"])
def test_run_mesh_setup_counts_cohort_rows(draws, want):
    """The set-up span gives the rows of each shard round's gathered
    cohort, 0 where the round steps every writer; each round's account
    span then says how many blocks of them the round took (one, as the
    capacity holds these cohorts), and the sync reads what it always
    does."""
    from test_cohort_gather import make_opt, mlr_task
    o = make_opt("dense-reference")
    _, spans = _traced(lambda: run_mesh(
        o, mlr_task(), ROUNDS, scenario=MeshScenario(seed=3, **draws)))
    setup, = _named(spans, "run_mesh/setup")
    assert setup.stats["cohort_rows"] == want
    chunks = [s.stats.get("cohort_chunks")
              for s in _named(spans, "run_mesh/account")]
    assert chunks == [1 if want else None] * ROUNDS
    assert [s.stats["reads"] for s in _named(spans, "run_mesh/sync")] == \
        [7 + 1 + 1] * ROUNDS


def test_run_mesh_same_with_profiler_on_and_off(mesh_run, mesh_traced):
    traced, _ = mesh_traced
    plain = mesh_run()
    exactness.assert_close_run(
        traced, plain, "profiler",
        floats=("objective", "agg_grad_sqnorm", "energy_cum", "wall_clock"))


# ------------------------------------------------------------------- train
STEPS, LOG_EVERY = 3, 2           # logs at steps 0 and 2, not 1


@pytest.fixture(scope="module")
def train_run():
    cfg = get("chb-paper-lm-124m").reduced()
    tc = TrainConfig(algorithm="chb", num_workers=2, alpha=0.05,
                     global_batch=4, seq_len=32, steps=STEPS,
                     log_every=LOG_EVERY)
    return lambda: train(cfg, tc, verbose=False)


@pytest.fixture(scope="module")
def train_traced(train_run):
    return _traced(train_run)


def test_train_spans_nest_by_phase(train_traced):
    _, spans = train_traced
    assert {s.name for s in spans} == TRAIN_SPANS
    call, = _named(spans, "train")
    assert all(call.holds(s) for s in spans)
    setup, = _named(spans, "train/setup")
    steps = _named(spans, "train/step")
    assert setup.end <= steps[0].start
    assert [s.stats["step_num"] for s in steps] == list(range(STEPS))
    for st in steps:
        inner = [s.name for s in spans if st.holds(s) and s is not st]
        assert inner[:2] == ["train/data", "train/dispatch"]


def test_train_log_span_only_at_logging_steps(train_traced):
    (_, _, history), spans = train_traced
    logged = [st.stats["step_num"] for st in _named(spans, "train/step")
              for lg in _named(spans, "train/log") if st.holds(lg)]
    assert logged == [h["step"] for h in history] == [0, 2]


def test_train_same_with_profiler_on_and_off(train_run, train_traced):
    (_, t_state, t_hist), _ = train_traced
    _, p_state, p_hist = train_run()
    np.testing.assert_array_equal(np.asarray(t_state.comm.uplink_count),
                                  np.asarray(p_state.comm.uplink_count))
    assert [h["comms"] for h in t_hist] == [h["comms"] for h in p_hist]
    exactness.assert_close([h["loss"] for h in t_hist],
                           [h["loss"] for h in p_hist], "profiler")

"""The readers of the program's host spans (bench/spans.py and the six
per-layer metrics over it), on hand-made events, on a traced CPU run of each
driver, and on recorded chip traces."""
import gzip
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, spans, tracing  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
HOST, DEV0, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
FED = ["round_host_ms.fed", "round_p95_ms.fed", "round_reads.fed",
       "call_setup_s.fed"]
TRAIN = ["call_setup_s.train", "data_wait_ms.train"]


def _ev(name, start, dur, plane=HOST, line="python3", **stats):
    return tracing.Event(plane, line, name, start, dur, stats)


def _op(start, dur, plane=DEV0):
    return tracing.Event(plane, tracing.OPS_LINE, "fusion", start, dur, {})


def _read(metric, red):
    return harness.reader_for(metric).read(None, None, red)


def _fed_window():
    """A window of 1000 ns holding one call of three rounds (times in ns):

      call 100..950, its first dispatch 150..200
      round 0  150..350: sync 220..300 (reads 9)
      round 1  350..550: sync 400..500 (reads 9)
      round 2  550..900: sync 600..650 and a second sync 660..700 (reads 15)
    """
    return tracing.Reduction([
        _ev(tracing.WINDOW, 0, 1000),
        _ev("run_mesh", 100, 850),
        _ev("run_mesh/round", 150, 200, step_num=0),
        _ev("run_mesh/dispatch", 150, 50),
        _ev("run_mesh/sync", 220, 80, reads=9),
        _ev("run_mesh/round", 350, 200, step_num=1),
        _ev("run_mesh/dispatch", 360, 30),
        _ev("run_mesh/sync", 400, 100, reads=9),
        _ev("run_mesh/round", 550, 350, step_num=2),
        _ev("run_mesh/sync", 600, 50, reads=15),
        _ev("run_mesh/sync", 660, 40, reads=15),
        # a span outside the window, and the device's own step line
        _ev("run_mesh/round", 1200, 500, step_num=3),
        _ev("run_mesh/round", 150, 200, plane=DEV0, line="Steps"),
    ])


def _train_window(dev=DEV0):
    """A window of 1000 ns: call 50..900, first dispatch 100..400, and three
    steps whose data spans are 410..460, 600..700 and 800..820; the device
    runs 0..430, 450..650 and 690..1000."""
    return tracing.Reduction([
        _ev(tracing.WINDOW, 0, 1000),
        _ev("train", 50, 850),
        _ev("train/step", 90, 330, step_num=0),
        _ev("train/dispatch", 100, 300),
        _ev("train/step", 410, 190, step_num=1),
        _ev("train/data", 410, 50),
        _ev("train/step", 600, 200, step_num=2),
        _ev("train/data", 600, 100),
        _ev("train/dispatch", 700, 10),
        _ev("train/step", 800, 100, step_num=3),
        _ev("train/data", 800, 20),
        _op(0, 430, dev), _op(450, 200, dev), _op(690, 310, dev),
        # another chip is busy throughout: only the first one counts
        _op(0, 1000, DEV1),
    ])


# ---------------------------------------------------------- bench/spans.py
def test_find_keeps_host_spans_inside_the_window_in_order():
    red = _fed_window()
    rounds = spans.find(red, "run_mesh/round")
    assert [r.stats["step_num"] for r in rounds] == [0, 1, 2]
    assert all(r.plane == HOST for r in rounds)
    assert spans.find(red, "train/step") == []


def test_self_time_leaves_out_the_children():
    red = _fed_window()
    rounds = spans.find(red, "run_mesh/round")
    assert spans.self_ns(rounds, spans.find(red, "run_mesh/sync")) == \
        [120.0, 100.0, 260.0]
    assert spans.self_ns(rounds, []) == [200.0, 200.0, 350.0]


def test_cover_merges_overlaps_and_clips_to_the_interval():
    cover = spans.Cover([(10, 30), (20, 40), (50, 60), (0, 5)])
    assert cover.merged == [[0, 5], [10, 40], [50, 60]]
    assert cover.within(0, 100) == 45
    assert cover.within(25, 55) == 20
    assert cover.within(41, 49) == 0


def test_idle_under_spans_counts_the_first_chip_alone():
    red = _train_window()
    data = spans.find(red, "train/data")
    # 430..450 under the first data span, 650..690 under the second, the
    # third wholly busy
    assert spans.idle_ns(red, data) == 60.0
    # no device planes at all (a CPU trace): the spans are all idle
    cpu = tracing.Reduction([e for e in red.events
                             if not e.plane.startswith("/device")])
    assert spans.idle_ns(cpu, spans.find(cpu, "train/data")) == 170.0


@pytest.mark.parametrize("q,want", [(95, 95), (50, 50), (100, 100), (1, 1),
                                    (0, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert spans.percentile(list(range(100, 0, -1)), q) == want


def test_call_setup_ends_at_the_first_dispatch_inside_the_call():
    red = _fed_window()
    assert spans.call_setup_s(red, "run_mesh", "run_mesh/dispatch") == \
        pytest.approx(100e-9)
    assert spans.call_setup_s(red, "train", "train/dispatch") is None


# ----------------------------------------------------- readers, hand-made
def test_round_host_ms_is_the_mean_round_time_outside_sync():
    assert _read("round_host_ms.fed", _fed_window()) == \
        pytest.approx((120 + 100 + 260) / 3 * 1e-6)


def test_round_p95_ms_is_the_slowest_of_three_rounds():
    assert _read("round_p95_ms.fed", _fed_window()) == pytest.approx(350e-6)


def test_round_reads_is_the_mean_reads_stat_of_the_syncs():
    assert _read("round_reads.fed", _fed_window()) == 12.0


def test_call_setup_s_fed_reads_the_call_and_its_first_dispatch():
    assert _read("call_setup_s.fed", _fed_window()) == pytest.approx(100e-9)


def test_call_setup_s_train_reads_the_call_and_its_first_dispatch():
    assert _read("call_setup_s.train", _train_window()) == \
        pytest.approx(350e-9)


def test_data_wait_ms_is_idle_under_data_per_step():
    assert _read("data_wait_ms.train", _train_window()) == \
        pytest.approx(60e-6 / 4)


def _without_program_spans(red):
    """The same window as an older program gives it: no spans of its own."""
    return tracing.Reduction([e for e in red.events
                              if not e.name.startswith(("run_mesh", "train"))])


@pytest.mark.parametrize("metric", FED + TRAIN)
def test_a_program_without_spans_reads_none(metric):
    for red in (_fed_window(), _train_window()):
        assert _read(metric, _without_program_spans(red)) is None


def test_the_new_metrics_are_declared_for_their_cells():
    bench = harness.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    fed_cells = [w["name"] for w in bench["workloads"]
                 if w["name"].startswith("fed.")]
    for name in FED + TRAIN:
        m = declared[name]
        assert m["source"] == "device_trace"
        assert m["workloads"] == (fed_cells if name in FED
                                  else ["train.lm124m"])


# ------------------------------------------------ a traced CPU driver run
FED_SMALL = {"clients": 8, "train_images": 80, "max_samples": 16}
TRAIN_SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
               "vocab_size": 256}
TRAIN_JOB = {"global_batch": 8, "seq_len": 32}


def _traced_cpu_run(cell, config=None, traffic=None):
    """One run of ``cell``'s driver on a CPU device with the window traced:
    the outcome and the trace's reduction."""
    c = harness.find_cell(cell)
    c.config = {**c.config, **(config or {})}
    c.traffic = {**c.traffic, **(traffic or {})}
    harness.configure_jax()
    counter, tracer = harness.CompileCounter(), tracing.Tracer()
    ctx = harness.Context(cell=c, seed=2 ** 31 + 99, seconds=0.5, trace=True,
                          devices=jax.devices()[:1], counter=counter,
                          tracer=tracer)
    try:
        out = harness.driver_for(c).run(ctx)
        red = tracer.reduce()
    finally:
        counter.close()
        tracer.close()
    return out, red


def test_traced_cpu_fed_run_reads_every_fed_metric():
    out, red = _traced_cpu_run("fed.emnist.full", FED_SMALL)
    values = {m: _read(m, red) for m in FED}
    rounds = spans.find(red, "run_mesh/round")
    assert len(rounds) == out.attempted
    assert values["round_reads.fed"] == 9.0          # 7 + 2K at K = 1
    slowest_ms = 1e-6 * max(r.dur_ns for r in rounds)
    assert 0.0 < values["round_host_ms.fed"] <= slowest_ms
    assert 0.0 < values["round_p95_ms.fed"] <= slowest_ms
    assert 0.0 < values["call_setup_s.fed"] < out.window_s


def test_traced_cpu_train_run_reads_every_train_metric():
    out, red = _traced_cpu_run("train.lm124m", TRAIN_SMALL, TRAIN_JOB)
    assert len(spans.find(red, "train/step")) == out.attempted
    assert 0.0 < _read("call_setup_s.train", red) < out.window_s
    # a CPU trace has no device plane: all of a data span is idle
    data = spans.find(red, "train/data")
    assert _read("data_wait_ms.train", red) == pytest.approx(
        1e-6 * sum(d.dur_ns for d in data) / out.attempted)


# ------------------------------------------------- recorded chip traces
def _recorded(name):
    """A traced window recorded on one TPU v5e: the program's host spans,
    with their stats, and the first chip's operations (as their union,
    ``busy``, where the full list would be too large to keep)."""
    with gzip.open(DATA / name, "rt") as f:
        return tracing.Reduction([tracing.Event(*row)
                                  for row in json.load(f)])


FED_TRACE = "fed_emnist_full.spans.v5e.events.json.gz"
TRAIN_TRACE = "train_lm124m.spans.v5e.events.json.gz"

# what the harness printed for these windows on the chip, from the whole
# trace
RECORDED = [
    (FED_TRACE, "round_host_ms.fed", 47.906451),
    (FED_TRACE, "round_p95_ms.fed", 233.163345),
    (FED_TRACE, "round_reads.fed", 9.0),
    (FED_TRACE, "call_setup_s.fed", 0.249500095),
    (TRAIN_TRACE, "call_setup_s.train", 1.551313732),
    (TRAIN_TRACE, "data_wait_ms.train", 0.9283757272727272),
]


@pytest.mark.parametrize("trace,metric,chip", RECORDED,
                         ids=[m for _, m, _ in RECORDED])
def test_recorded_chip_trace_reads_what_the_chip_run_printed(trace, metric,
                                                              chip):
    assert _read(metric, _recorded(trace)) == pytest.approx(chip, rel=1e-9)


def test_recorded_fed_window_is_five_rounds_whose_idle_is_under_leaves():
    """fed.emnist.full, 5 rounds of 3,400 writers: the first round's
    dispatch traces and lowers the programs; every other round waits ~60 ms
    in its sync for the device's round, then reads nine arrays."""
    red = _recorded(FED_TRACE)
    rounds = spans.find(red, "run_mesh/round")
    assert [r.stats["step_num"] for r in rounds] == list(range(5))
    syncs = spans.find(red, "run_mesh/sync")
    assert all(55e6 < s.dur_ns < 65e6 for s in syncs[1:])
    leaves = ["run_mesh/setup", "run_mesh/dispatch", "run_mesh/sync",
              "run_mesh/account", "run_mesh/history"]
    under = sum(spans.idle_ns(red, spans.find(red, n)) for n in leaves)
    idle = red.window_s * 1e9 - red.busy_s(red.device_planes()[0]) * 1e9
    assert under / idle > 0.95


def test_recorded_train_window_logs_at_steps_zero_and_ten():
    red = _recorded(TRAIN_TRACE)
    steps = spans.find(red, "train/step")
    assert [s.stats["step_num"] for s in steps] == list(range(11))
    logged = [s.stats["step_num"] for s in steps
              for lg in spans.find(red, "train/log")
              if s.start_ns <= lg.start_ns < s.end_ns]
    assert logged == [0, 10]

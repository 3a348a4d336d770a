"""The trace reduction, on hand-made events and on a recorded chip trace."""
import collections
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import tracing, work  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _ev(plane, name, start, dur, line=tracing.OPS_LINE, stats=None):
    return tracing.Event(plane, line, name, start, dur, stats or {})


def _reduction():
    return tracing.Reduction([
        _ev("/host:CPU", tracing.WINDOW, 100, 1000, line="python"),
        _ev("/host:CPU", "PjitFunction(shard_round)", 100, 1000,
            line="tf_pjrt"),
        _ev("/host:CPU", "np.asarray(jax.Array)", 600, 300, line="tf_pjrt"),
        # device 0: two overlapping ops, one before the window (clipped)
        _ev(DEV0, "fusion.1", 50, 150),       # 100..200 inside
        _ev(DEV0, "fusion.2", 150, 150),      # 150..300, overlaps
        _ev(DEV0, "all-reduce.3", 900, 100),  # 900..1000
        # device 1
        _ev(DEV1, "fusion.1", 200, 400),
        # not an op line: ignored
        _ev(DEV0, "step", 100, 1000, line="Steps"),
    ])


def test_busy_is_the_union_clipped_to_the_window():
    red = _reduction()
    assert red.window_s == pytest.approx(1000e-9)
    assert red.device_planes() == [DEV0, DEV1]
    assert red.busy_s(DEV0) == pytest.approx(300e-9)   # 100..300, 900..1000
    assert red.busy_s(DEV1) == pytest.approx(400e-9)
    assert red.mean_busy_s(1) == pytest.approx(300e-9)
    assert red.mean_busy_s(2) == pytest.approx(350e-9)


def test_op_seconds_top_ops_and_idle_gaps():
    red = _reduction()
    assert red.op_seconds(lambda e: "all-reduce" in e.name, 2) == \
        pytest.approx(100e-9)
    top = red.top_ops(10, 1)
    assert top[0][0] == "fusion.2" and top[0][1] == pytest.approx(150e-9)
    gaps = red.idle_gaps(2)
    # 300..900 is the longest gap; the innermost host event over its middle
    assert gaps[0][0] == "np.asarray(jax.Array)" and gaps[0][1] == pytest.approx(600e-9)
    assert gaps[1][1] == pytest.approx(100e-9)


def test_kernel_call_reads_name_and_shapes_from_the_hlo():
    # as a v5e compile prints a Pallas kernel of the fed round
    hlo = ("%tree_censor_bank_advance.2 = f32[16,512,128]{2,1,0:T(8,128)S(1)} "
           "custom-call(%compare_convert_fusion, %bitcast.33, %bitcast.35), "
           "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
           "{f32[16,1,1]{2,1,0}, f32[16,512,128]{2,1,0}, "
           "f32[16,512,128]{2,1,0}}, frontend_attributes={kernel_metadata={}}, "
           "metadata={op_name=\"jit(shard_round)/kernels/"
           "tree_censor_bank_advance/pallas_call\" stack_frame_id=60}")
    e = _ev(DEV0, "tree_censor_bank_advance.2", 0, 10,
            stats={"long_name": hlo})
    name, ops, res = tracing.kernel_call(e)
    assert name == "tree_censor_bank_advance"
    assert ops == [((16, 1, 1), 4), ((16, 512, 128), 4), ((16, 512, 128), 4)]
    assert res == [((16, 512, 128), 4)]
    assert tracing.kernel_call(_ev(DEV0, "fusion.3", 0, 10)) is None


def test_kernel_call_without_layout_constraints_reads_the_operands():
    hlo = ("%hb.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) custom-call("
           "f32[8,128]{1,0} %p0, f32[8,128]{1,0} %p1, f32[2]{0} %p2), "
           "custom_call_target=\"tpu_custom_call\"")
    name, ops, res = tracing.kernel_call(_ev(DEV0, "tree_hb_update.1", 0, 10,
                                             stats={"long_name": hlo}))
    assert name == "tree_hb_update"
    assert ops == [((8, 128), 4), ((8, 128), 4), ((2,), 4)]
    assert res == [((8, 128), 4), ((8, 128), 4)]


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError, match="bench_window"):
        tracing.Reduction([_ev(DEV0, "fusion.1", 0, 10)])


def _recorded():
    """Five rounds of fed.emnist.full (3,400 writers) traced on one TPU v5e:
    the device's op line and the host's events inside the window."""
    with gzip.open(DATA / "fed_emnist_full.v5e.events.json.gz", "rt") as f:
        return tracing.Reduction([tracing.Event(*row, {})
                                  for row in json.load(f)])


def test_recorded_chip_trace_reduces_to_busy_and_window():
    red = _recorded()
    assert red.device_planes() == [DEV0]
    assert red.window_s == pytest.approx(0.81096231, rel=1e-6)
    assert red.mean_busy_s(1) == pytest.approx(0.453694544, rel=1e-6)
    summary = red.device_summary(1)
    assert len(summary["breakdown"]["device_ops"]) == 10
    assert summary["breakdown"]["device_ops"][0][0].startswith("%fusion = ")
    assert len(summary["breakdown"]["idle_gaps"]) == 10


def test_recorded_chip_trace_names_every_kernel_with_its_shapes():
    red = _recorded()
    names, need_s, busy_s = collections.Counter(), 0.0, 0.0
    for e in red.ops():
        call = tracing.kernel_call(e)
        if call is None:
            continue
        names[call[0]] += 1
        if call[0] == "tree_censor_bank_advance":
            # W's 48,608 weights a writer as 512 rows of 128 lanes, or b's
            rows = call[2][0][0][1]
            assert rows in (512, 1)
            assert call[1] == [((3400, 1, 1), 4), ((3400, rows, 128), 4),
                               ((3400, rows, 128), 4)]
            assert call[2] == [((3400, rows, 128), 4)]
        need_s += work.kernel_bytes(*call) / 819e9
        busy_s += e.dur_ns * 1e-9
    # two leaves (W and b) a round, five rounds
    assert names == {"tree_delta_sqnorms": 10, "tree_censor_bank_advance": 10,
                     "tree_hb_update": 10}
    assert 0.0 < 100.0 * need_s / busy_s <= 100.0

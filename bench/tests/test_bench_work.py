"""The benchmark's FLOP and byte functions against hand counts: the
model-independent ones in bench/work.py and each configuration's own in
bench/configs/<config>.work.py."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, work  # noqa: E402


def _lm124m():
    return harness.load_json("configs", "lm124m")


def _emnist():
    return harness.load_json("configs", "emnist62-mlr")


def _work(config):
    return harness.config_part(config, "work")


def test_lm_matmul_params_hand_count():
    # per layer: q, k, v, o at 768 x 768 and three 768 x 3072 SwiGLU
    # matrices; plus the 768 x 32768 head (the embedding is a gather)
    per_layer = 4 * 768 * 768 + 3 * 768 * 3072
    assert per_layer == 9_437_184
    assert _work("lm124m").matmul_params(_lm124m()) == \
        12 * per_layer + 768 * 32768


def test_lm_flops_per_token_hand_count():
    cfg = _lm124m()
    # 6 per matmul weight; causal attention at T=1024 sees 512.5 keys on
    # average, 4 x 768 x 512.5 forward FLOPs per layer, x 3 with backward
    want = 6 * 138_412_032 + 3 * 12 * 4 * 768 * 512.5
    assert _work("lm124m").flops_per_token(cfg, 1024) == pytest.approx(want)
    # the paper-style 6N + 12 L T d bound without the causal half is larger
    assert want < 6 * 138_412_032 + 12 * 12 * 1024 * 768


def test_mlr_round_work_hand_count():
    # 2 writers, 10 samples: logits and weight gradient 4 x 10 x 785 x 62,
    # images 10 x (784 x 4 + 4) bytes, two bank rows read and written
    w = _work("emnist62-mlr").round_work(_emnist(), [4, 6])
    assert w["flops"] == 4 * 10 * 785 * 62
    assert w["bytes"] == 10 * 3140 + 2 * 2 * 48_670 * 4


def test_full_population_round_is_memory_bound_at_4_ms():
    cfg = _emnist()
    counts = harness.config_part("emnist62-mlr", "task").sample_counts(cfg, 7)
    w = _work("emnist62-mlr").round_work(cfg, counts)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = work.roofline_seconds(w["flops"], w["bytes"], peaks, 1)
    assert least == pytest.approx(w["bytes"] / 819e9)
    assert 4.0e-3 < least < 4.5e-3
    assert work.roofline_seconds(w["flops"], w["bytes"], peaks, 4) == \
        pytest.approx(least / 4)


@pytest.mark.parametrize("name", sorted(work.KERNEL_BYTES))
def test_kernel_bytes_stream_every_operand_once(name):
    # (M, rows, 128) f32 grads and bank in, the bank out
    blk = ((3400, 384, 128), 4)
    assert work.kernel_bytes(name, [blk, blk], [blk]) == 3 * 3400 * 384 * 128 * 4


def test_unknown_kernel_is_an_error():
    with pytest.raises(KeyError, match="no byte count"):
        work.kernel_bytes("_some_new_kernel", [], [])

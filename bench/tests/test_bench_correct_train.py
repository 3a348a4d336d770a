"""``correct`` of the trainer cell, driven on the CPU at a tiny size.

A sound run through ``train()`` is correct; a run with the timed path broken
underneath is not, for each fault a one-chip trainer cell can have; and the
control (the reference in bfloat16, in the program's place) fails the
cell's limits.
"""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import control, harness  # noqa: E402

SEED = 2 ** 31 + 777
SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256}
JOB = {"global_batch": 8, "seq_len": 32}


def _run():
    return harness.run_cell("train.lm124m", SEED, 0.5, False,
                            devices=jax.devices()[:1], config_overrides=SMALL,
                            traffic_overrides=JOB)


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _wrap_step(monkeypatch, change):
    from repro.core import distributed
    real = distributed.make_scan_step

    def make(cfg, loss_fn):
        step = real(cfg, loss_fn)

        def broken(params, state, batch):
            return change(params, *step(params, state, batch))
        return broken
    monkeypatch.setattr(distributed, "make_scan_step", make)


def _frozen(monkeypatch):
    _wrap_step(monkeypatch, lambda old, new, state, m: (old, state, m))


def _altered(monkeypatch):
    _wrap_step(monkeypatch, lambda old, new, state, m: (
        new, state, {**m, "loss": m["loss"] * 1.01}))


def _window_other_data(monkeypatch):
    """Only the window's call (the one logged every ``log_every`` steps)
    goes wrong: it trains from other weights on other data, so its updates
    are not those the checked calls made. Its program stays the same, so
    nothing compiles inside the window."""
    import dataclasses

    from repro.train import trainer
    real = trainer.train

    def train(cfg, tc, *a, **kw):
        if tc.log_every > 1:
            tc = dataclasses.replace(tc, seed=tc.seed + 1)
        return real(cfg, tc, *a, **kw)
    monkeypatch.setattr(trainer, "train", train)


def _half(monkeypatch):
    from repro.models import model
    real = model.train_loss

    def half(params, cfg, batch, **kw):
        rows = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:rows] for k, v in batch.items()},
                    **kw)
    monkeypatch.setattr(model, "train_loss", half)


@pytest.mark.parametrize("fault", [_frozen, _half, _altered,
                                   _window_other_data],
                         ids=["state_unchanged", "half_the_batch",
                              "answer_altered", "window_other_data"])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = _run()
    assert not line["correct"], line["checks"]


def test_control_and_half_batch_fail_the_limits():
    cell = harness.find_cell("train.lm124m")
    cell.config.update(SMALL)
    cell.traffic.update(JOB)
    harness.configure_jax()
    lim = cell.config["limits"]
    for r in control.train_variants(cell, SEED):
        assert any(r[k] > lim[k] for k in lim), r

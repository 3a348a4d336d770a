"""``correct`` of the fed cells, driven on the CPU at a tiny size.

A sound run is correct; a run with the timed path broken underneath it is
not, for each fault a fed cell can have; and the control (the reference
one precision lower, in the program's place) fails the cell's limits.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402

SEED = 2 ** 31 + 4242
SMALL = {"clients": 8, "train_images": 80, "max_samples": 16}
# enough participants among 8 writers that draws, losses and quorum all act
COHORT = {"participation": 0.6}


def _run(cell, traffic=None):
    return harness.run_cell(cell, SEED, 0.5, False,
                            devices=jax.devices()[:1], config_overrides=SMALL,
                            traffic_overrides=traffic)


@pytest.mark.parametrize("cell,traffic", [("fed.emnist.full", None),
                                          ("fed.emnist.cohort", COHORT)])
def test_sound_run_is_correct(cell, traffic):
    line = _run(cell, traffic)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"fed_rounds_per_s", "setup_s"}


def _frozen(monkeypatch):
    from repro.opt.optimizer import ComposedOptimizer
    monkeypatch.setattr(ComposedOptimizer, "apply_server",
                        lambda self, params, prev, agg: params)


def _half(monkeypatch):
    task_mod = harness.config_part("emnist62-mlr", "task")
    make = task_mod.make_task

    def make_half(cfg, data):
        t = make(cfg, data)
        keep = lambda d: jnp.where(d["n"] % 2 == 0, 2.0, 0.0)  # noqa: E731
        return t._replace(
            grad_fn=lambda p, d: jax.tree_util.tree_map(
                lambda g: keep(d) * g, t.grad_fn(p, d)),
            loss_fn=lambda p, d: keep(d) * t.loss_fn(p, d))
    monkeypatch.setattr(task_mod, "make_task", make_half)


def _altered(monkeypatch):
    import repro.fed
    real = repro.fed.run_mesh

    def run_mesh(*a, **kw):
        h = real(*a, **kw)
        att = h.attempted.copy()
        att[1] -= 1
        return h._replace(attempted=att)
    monkeypatch.setattr(repro.fed, "run_mesh", run_mesh)


@pytest.mark.parametrize("fault", [_frozen, _half, _altered],
                         ids=["state_unchanged", "half_the_batch",
                              "answer_altered"])
@pytest.mark.parametrize("cell,traffic", [("fed.emnist.full", None),
                                          ("fed.emnist.cohort", COHORT)])
def test_fault_is_not_correct(monkeypatch, fault, cell, traffic):
    fault(monkeypatch)
    line = _run(cell, traffic)
    assert not line["correct"], line["checks"]


_X4 = """
import json, sys
sys.path[:0] = [{root!r} + "/src", {root!r}]
import jax
from jax.sharding import PartitionSpec as P
from bench import harness
import repro.fed.mesh as mesh_mod

def run():
    return harness.run_cell("fed.emnist.x4", {seed}, 0.5, False,
                            devices=jax.devices()[:4],
                            config_overrides={small!r})["correct"]

def no_fold(mesh, axis="clients"):
    # each shard keeps its own partial: the exchange is left out
    return jax.shard_map(
        lambda st: jax.tree_util.tree_map(lambda v: v[0], st), mesh=mesh,
        in_specs=(P(axis),), out_specs=P(), axis_names={{axis}},
        check_vma=False)

sound = run()
mesh_mod.make_client_fold = no_fold
print(json.dumps({{"sound": sound, "no_fold": run()}}))
"""


def test_x4_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _X4.format(root=str(ROOT), seed=SEED, small=SMALL)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_fold": False}


def test_control_and_planted_faults_fail_the_limits():
    cell = harness.find_cell("fed.emnist.full")
    cell.config.update(SMALL)
    harness.configure_jax()
    rows = {r["variant"]: r for r in control.fed_variants(
        cell, SEED, jax.devices()[:1])}
    lim = cell.config["limits"]
    for variant in ("control_bf16", "fault_half"):
        r = rows[variant]
        assert any(r[k] > lim[k] for k in lim) or r["count_mismatch"] > 0, r
    assert np.isfinite(rows["control_bf16"]["objective_gap"])

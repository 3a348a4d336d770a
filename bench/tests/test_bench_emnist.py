"""The emnist62-mlr population and task at a tiny size on the CPU."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

SEED = 2 ** 31 + 17


def _cfg(**kw):
    cfg = harness.load_json("configs", "emnist62-mlr")
    return {**cfg, **kw}


def test_counts_are_one_multiset_in_a_seeded_order():
    task = harness.config_part("emnist62-mlr", "task")
    cfg = _cfg()
    a, b = task.sample_counts(cfg, SEED), task.sample_counts(cfg, 7)
    assert a.sum() == cfg["train_images"] == 671_585
    assert a.max() == cfg["max_samples"] and a.min() > 0
    assert len(a) == cfg["clients"] == 3400
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))


def test_population_does_not_depend_on_the_split():
    task = harness.config_part("emnist62-mlr", "task")
    cfg = _cfg(clients=8, train_images=80, max_samples=16)
    counts = task.sample_counts(cfg, SEED)
    whole = task.make_block(cfg, SEED, 0, 8, counts)
    halves = [task.make_block(cfg, SEED, i * 4, 4, counts) for i in (0, 1)]
    for k in whole:
        np.testing.assert_array_equal(
            np.asarray(whole[k]),
            np.concatenate([np.asarray(h[k]) for h in halves]))
    n = np.asarray(whole["n"])
    assert np.array_equal(n, counts)
    x = np.asarray(whole["x"])
    for m in range(8):        # padded rows are zero
        assert not x[m, n[m]:].any()


@pytest.mark.parametrize("nonzero", [False, True])
def test_task_gradient_matches_autodiff(nonzero):
    task = harness.config_part("emnist62-mlr", "task")
    cfg = _cfg(clients=4, train_images=40, max_samples=16)
    counts = task.sample_counts(cfg, SEED)
    data = task.make_block(cfg, SEED, 0, 4, counts)
    t = task.make_task(cfg, data)
    params = t.init_params
    if nonzero:
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        params = {"W": 0.1 * jax.random.normal(k1, params["W"].shape),
                  "b": 0.1 * jax.random.normal(k2, params["b"].shape)}
    for m in range(4):
        dm = jax.tree_util.tree_map(lambda v: v[m], data)
        got = t.grad_fn(params, dm)
        want = jax.grad(t.loss_fn)(params, dm)
        for k in got:
            np.testing.assert_allclose(got[k], want[k], rtol=2e-5,
                                       atol=1e-7)
    # sum_m f_m at zero weights is the mean cross-entropy of a uniform
    # guess over the 62 classes
    if not nonzero:
        total = sum(float(t.loss_fn(params, jax.tree_util.tree_map(
            lambda v: v[m], data))) for m in range(4))
        assert total == pytest.approx(np.log(62), rel=1e-6)


def test_reference_matches_the_tasks_loss_and_draws():
    task = harness.config_part("emnist62-mlr", "task")
    ref = harness.config_part("emnist62-mlr", "ref")
    cfg = _cfg(clients=8, train_images=80, max_samples=16)
    data = task.make_block(cfg, SEED, 0, 8, task.sample_counts(cfg, SEED))
    r = ref.run(cfg, data, rounds=2, participation=1.0, loss_prob=0.0,
                quorum=1.0, seed=SEED)
    assert r["objective"][0] == pytest.approx(np.log(62), rel=1e-6)
    assert r["attempted"][0] == 8 and r["quorum_met"].all()
    assert r["objective"][1] < r["objective"][0]
    u = ref._draws(SEED, 3, jnp.arange(8, dtype=jnp.uint32))
    assert all(0.0 <= float(v) < 1.0 for v in np.ravel(u))

"""``run_mesh``'s gathered cohort round against the round that steps every
writer.

Where participation is drawn and small (``fed.mesh.cohort_capacity``), a
shard round draws first, gathers its participants into blocks of C rows,
computes their gradients and steps their bank, transport and count rows
only, then scatters them back (``ComposedOptimizer.shard_step(capacity=
C)``). These tests hold that round to the full one on the same draws,
which they force by setting the capacity rule to 0, as
tests/test_bank_tiles.py forces the untiled bank.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import opt
from repro.core.simulator import FedTask
from repro.fed import mesh as fed_mesh
from repro.fed.mesh import MeshScenario, cohort_capacity, run_mesh
from repro.opt import AdaptiveCensor

TESTS = os.path.dirname(os.path.abspath(__file__))
M, ROUNDS = 200, 8
PIXELS, CLASSES, SAMPLES = 16, 6, 8
SCENARIO = dict(participation=0.05, loss_prob=0.2, quorum=0.8, seed=3)
EXACT = ("mask", "participated", "attempted", "delivered", "quorum_met",
         "comm_cum", "delivered_cum", "bytes_cum")
# objective and theta: the cohort's gradients are the same row math in a
# batch of C rows; allowed to differ by float rounding, not by a decision
RTOL, ATOL = exactness.BOUNDS["cohort_gather"]
ROUTES = {"dense-reference": {}, "dense-pallas": {"backend": "pallas"},
          "int8-reference": {"quantize": "int8"},
          "int8-pallas": {"quantize": "int8", "backend": "pallas"}}


def mlr_task(seed: int = 0) -> FedTask:
    """Multinomial logistic regression over M writers, small enough that
    the interpreted kernels step every writer quickly: a W leaf and a
    bias leaf."""
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, (M, SAMPLES, PIXELS), jnp.float32)
    y = jax.random.randint(ky, (M, SAMPLES), 0, CLASSES)
    scale = jnp.float32(1.0 / (M * SAMPLES))

    def logits(p, d):
        return d["x"] @ p["W"] + p["b"]

    def grad_fn(p, d):
        r = (jax.nn.softmax(logits(p, d), axis=-1)
             - jax.nn.one_hot(d["y"], CLASSES, dtype=jnp.float32)) * scale
        return {"W": d["x"].T @ r, "b": jnp.sum(r, axis=0)}

    def loss_fn(p, d):
        z = logits(p, d)
        gold = jnp.sum(z * jax.nn.one_hot(d["y"], CLASSES, dtype=z.dtype),
                       axis=-1)
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold) * scale

    init = {"W": jnp.zeros((PIXELS, CLASSES), jnp.float32),
            "b": jnp.zeros((CLASSES,), jnp.float32)}
    return FedTask(init_params=init, grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data={"x": x, "y": y}, name="mlr")


def make_opt(route: str):
    if route == "csgd":
        return opt.make("csgd", 0.5, M, tau0=1e-6, seed=1)
    kw = ROUTES.get(route, {"quantize": route})     # topk, lowrank
    return opt.make("chb", 0.5, M, beta=0.4, eps1_scale=0.5, **kw)


def compare(route: str, shards: int = 1, capacity=None) -> dict:
    """The gathered and the full round over ``shards`` shards on the same
    draws: which histories are equal, how far the floats moved apart, and
    whether the draws left the decisions in play."""
    from repro.launch.mesh import make_client_mesh

    task, o = mlr_task(), make_opt(route)
    rule = fed_mesh.cohort_capacity

    def run(cap):
        if cap is not None:
            fed_mesh.cohort_capacity = lambda *a, **k: cap
        try:
            return run_mesh(o, task, ROUNDS, mesh=make_client_mesh(shards),
                            scenario=MeshScenario(**SCENARIO), donate=True,
                            bake_data=False)
        finally:
            fed_mesh.cohort_capacity = rule

    gathered, full = run(capacity), run(0)

    def flat(h):
        return np.concatenate([np.ravel(np.asarray(x)) for x in
                               jax.tree_util.tree_leaves(h.final_params)])
    out = {f: bool(np.array_equal(getattr(gathered, f), getattr(full, f)))
           for f in EXACT}
    out["objective_close"] = bool(np.allclose(
        gathered.objective, full.objective, rtol=RTOL, atol=ATOL))
    out["params_close"] = bool(np.allclose(flat(gathered), flat(full),
                                           rtol=RTOL, atol=ATOL))
    out["mixed_mask"] = bool(0 < gathered.mask.sum() < gathered.mask.size)
    # a round where some cohort member was censored and another delivered
    out["censored_and_delivered"] = bool(np.any(
        (gathered.attempted < gathered.participated)
        & (gathered.delivered > 0)))
    out["largest_cohort"] = int(gathered.participated.max())
    return out


def _assert_same(out: dict, censored_and_delivered: bool = True):
    """Draws, masks, counts and bytes exact; objective and theta within
    RTOL/ATOL; and the draws left both decisions in play."""
    assert out["mixed_mask"], out
    if censored_and_delivered:
        assert out["censored_and_delivered"], out
    assert all(out[f] for f in EXACT), out
    assert out["objective_close"] and out["params_close"], out


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_gathered_cohort_matches_full_round_one_shard(route):
    _assert_same(compare(route))


@pytest.fixture(scope="module")
def two_shards():
    """Every route at K=2, in one child process that sees two CPU
    devices (this one keeps its single-device view)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        from test_cohort_gather import ROUTES, compare
        print(json.dumps({{r: compare(r, shards=2) for r in ROUTES}}))
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(TESTS, "..", "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_gathered_cohort_matches_full_round_two_shards(two_shards, route):
    _assert_same(two_shards[route])


@pytest.mark.parametrize("route", ["topk", "lowrank"])
def test_gathered_cohort_subsets_every_transport_row(route):
    """Top-k's EF bank and low-rank's EF bank and warm-start factors are
    gathered and scattered with the bank (no round of these draws both
    censors and delivers: each cohort member's EF residual ships)."""
    _assert_same(compare(route), censored_and_delivered=False)


def test_gathered_cohort_keys_stochastic_draws_by_id():
    """CSGD's censor draws are keyed by absolute id, so the cohort's rows
    draw what they draw in the full round."""
    _assert_same(compare("csgd"))


@pytest.mark.parametrize("route", ["dense-pallas", "int8-reference"])
def test_cohort_past_capacity_takes_more_blocks_exactly(route):
    """A capacity of 2 against cohorts of 5-12: every round takes several
    blocks, and still gives the full round's answer."""
    out = compare(route, capacity=2)
    assert out["largest_cohort"] > 2 * 2, out
    _assert_same(out)


def test_capacity_rule_engages_only_on_small_drawn_cohorts():
    censor = make_opt("dense-pallas").censor
    drawn = MeshScenario(**SCENARIO)
    # six standard deviations over the mean cohort, in whole sublanes
    assert cohort_capacity(M, drawn, censor) == 32
    assert cohort_capacity(3400, MeshScenario(participation=1 / 34,
                                              loss_prob=0.1), censor) == 160
    # no draws, a cohort over half the shard, or a metric bag that reads
    # every writer: the full round
    assert cohort_capacity(M, MeshScenario(), censor) == 0
    assert cohort_capacity(M, MeshScenario(participation=0.7,
                                           loss_prob=0.3), censor) == 0
    assert cohort_capacity(M, drawn, censor, collect_metrics=True) == 0
    # a censor that decides over the whole cohort cannot be split by row
    assert cohort_capacity(M, drawn, AdaptiveCensor(0.5)) == 0


def test_shard_step_capacity_needs_draws_and_a_per_client_censor():
    """The gathered step steps participants, so it needs the draw, and a
    censor that decides each writer from its own row."""
    import dataclasses
    task, o = mlr_task(), make_opt("dense-reference")

    def grads(rows):
        return jax.vmap(task.grad_fn, in_axes=(None, 0))(
            task.init_params, jax.tree_util.tree_map(
                lambda x: jnp.take(x, rows, axis=0, mode="clip"),
                task.worker_data))
    drawn = jnp.zeros((M,), jnp.float32).at[:3].set(1.0)
    with pytest.raises(ValueError, match="participate"):
        o.shard_step(o.shard_init(task.init_params), task.init_params,
                     grads, capacity=8)
    ema = dataclasses.replace(o, censor=AdaptiveCensor(0.5))
    with pytest.raises(ValueError, match="whole cohort"):
        ema.shard_step(ema.shard_init(task.init_params), task.init_params,
                       grads, participate=drawn, capacity=8)

"""``run_mesh``'s client bank held as the Pallas kernels' tiles.

On the pallas backend's dense transport, ``run_mesh`` keeps each shard's
bank leaves as the staged kernels' ``(M_local, R, 128)`` tiles from set-up
on (``ComposedOptimizer.bank_tiles`` / ``shard_init``): each round tiles
the fresh gradient once, runs both bank kernels on tiles, and untiles only
the eq.-(5) partial sum. These tests hold that route to the untiled
``shard_step`` it replaces, and each tiled kernel core to the padding
entry point that every other caller keeps.
"""
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
from repro.fed.mesh import MeshScenario, run_mesh
from repro.kernels import censor, common
from repro.kernels import ops as kernel_ops
from repro.opt.optimizer import ComposedOptimizer

TESTS = os.path.dirname(os.path.abspath(__file__))
M, ROUNDS = 8, 6
# W spans two 256-row blocks padded to 512 rows; b is one (1, 128) tile
PIXELS, CLASSES, SAMPLES = 300, 120, 12


def mlr_task(m: int = M, seed: int = 0) -> FedTask:
    """Multinomial logistic regression over ``m`` writers, in f32: a W
    leaf of two tile blocks and a bias leaf of one partial tile."""
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, (m, SAMPLES, PIXELS), jnp.float32)
    y = jax.random.randint(ky, (m, SAMPLES), 0, CLASSES)
    scale = jnp.float32(1.0 / (m * SAMPLES))

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


def pallas_chb(m: int = M):
    return opt.make("chb", 0.5, m, beta=0.4, eps1_scale=0.5,
                    backend="pallas")


def compare_routes(shards: int):
    """The tiled and the untiled route over ``shards`` shards, on the
    same draws, compute one run bit for bit (on the CPU the axis-0
    worker-sum over tiles adds in the order it does untiled)."""
    from repro.launch.mesh import make_client_mesh

    task, o = mlr_task(), pallas_chb()
    assert o.bank_tiles

    def run():
        return run_mesh(o, task, ROUNDS, mesh=make_client_mesh(shards),
                        scenario=MeshScenario(participation=0.75,
                                              loss_prob=0.2, quorum=0.5,
                                              seed=3),
                        donate=True, bake_data=False)

    tiled = run()
    prop = ComposedOptimizer.bank_tiles
    ComposedOptimizer.bank_tiles = property(lambda self: False)
    try:
        plain = run()
    finally:
        ComposedOptimizer.bank_tiles = prop

    # the draws must leave both decisions in play, or masks prove little
    assert 0 < tiled.mask.sum() < tiled.mask.size
    exactness.assert_bitwise(tiled, plain)


def test_tiled_bank_matches_untiled_one_shard():
    compare_routes(1)


def test_tiled_bank_matches_untiled_two_shards():
    """K=2, in a child process that sees two CPU devices."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        from test_bank_tiles import compare_routes
        compare_routes(2)
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(TESTS, "..", "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]


def test_bank_tiles_only_on_the_pallas_dense_route():
    assert pallas_chb().bank_tiles
    assert not opt.make("chb", 0.5, M).bank_tiles
    assert not opt.make("chb", 0.5, M, quantize="int8",
                        backend="pallas").bank_tiles


def test_tile_bank_round_trips_each_leaf():
    task, o = mlr_task(), pallas_chb()
    tiled = o.shard_init(task.init_params)
    assert tiled.ghat["W"].shape == (M, 512, 128)
    assert tiled.ghat["b"].shape == (M, 1, 128)
    plain = opt.make("chb", 0.5, M).shard_init(task.init_params)
    assert plain.ghat["W"].shape == (M, PIXELS, CLASSES)
    bank = jax.tree_util.tree_map(
        lambda h: jax.random.normal(jax.random.PRNGKey(1), h.shape,
                                    h.dtype), plain.ghat)
    for key, h in bank.items():
        h3 = common._pad_to_3d(h)
        assert h3.shape == tiled.ghat[key].shape
        for w in range(M):
            np.testing.assert_array_equal(
                np.asarray(common.untile(h3[w], h.shape[1:])),
                np.asarray(h[w]))
        n = int(np.prod(h.shape[1:]))
        assert not np.asarray(h3.reshape(M, -1)[:, n:]).any()


# ------------------------------------------------------ tiled kernel cores
SHAPES = {"two-blocks": (4, PIXELS, CLASSES), "one-row": (4, 62),
          "ragged": (3, 5, 7, 3)}


def _pair(shape):
    kg, kh = jax.random.split(jax.random.PRNGKey(7))
    return (jax.random.normal(kg, shape, jnp.float32),
            jax.random.normal(kh, shape, jnp.float32))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_delta_sqnorm_core_matches_padding_entry(name):
    g, h = _pair(SHAPES[name])
    want = censor.censor_delta_sqnorm_batched(g, h, interpret=True)
    got = censor.censor_delta_sqnorm_tiles(
        common._pad_to_3d(g), common._pad_to_3d(h), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("in_place", [False, True])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_bank_advance_core_matches_padding_entry(name, in_place):
    g, h = _pair(SHAPES[name])
    m = g.shape[0]
    mask = (jnp.arange(m) % 2).astype(jnp.float32)
    want = censor.censor_bank_advance(g, h, mask, interpret=True)
    h3 = common._pad_to_3d(h)
    got = jax.jit(lambda g3, h3: censor.censor_bank_advance_tiles(
        g3, h3, mask, in_place=in_place, interpret=True),
        donate_argnums=(1,) if in_place else ())(common._pad_to_3d(g), h3)
    assert got.shape == h3.shape
    n = int(np.prod(g.shape[1:]))
    flat = np.asarray(got).reshape(m, -1)
    np.testing.assert_array_equal(flat[:, :n].reshape(g.shape),
                                  np.asarray(want))
    assert not flat[:, n:].any()        # the padding stays zero


def test_tree_dispatch_on_tiles_matches_padding_entry():
    task = mlr_task()
    grads = jax.vmap(task.grad_fn, in_axes=(None, 0))(
        jax.tree_util.tree_map(lambda x: x + 0.01, task.init_params),
        task.worker_data)
    bank = jax.tree_util.tree_map(lambda g: 0.5 * g[::-1], grads)
    mask = (jnp.arange(M) % 3 == 0).astype(jnp.float32)
    g3, h3 = (jax.tree_util.tree_map(common._pad_to_3d, t)
              for t in (grads, bank))
    np.testing.assert_array_equal(
        np.asarray(kernel_ops.tree_delta_sqnorms(g3, h3, tiles=True)),
        np.asarray(kernel_ops.tree_delta_sqnorms(grads, bank)))
    want = kernel_ops.tree_censor_bank_advance(grads, bank, mask)
    got = kernel_ops.tree_censor_bank_advance(g3, h3, mask, tiles=True)
    for key, w in want.items():
        got_leaf = jax.vmap(lambda x, s=w.shape[1:]: common.untile(x, s))(
            got[key])
        np.testing.assert_array_equal(np.asarray(got_leaf), np.asarray(w))

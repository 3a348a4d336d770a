"""Buffer-donation safety: fused-step perf must never change bits.

``train/trainer.py`` jits its step with ``donate_argnums=(0, 1)`` and
``simulator.run(donate=True)`` donates ``init_params`` into the scan —
so XLA may overwrite any donated input buffer as soon as it likes. The
one invariant that makes this safe is the step-0 copy guard: every
``init`` (``ComposedOptimizer.init``, ``distributed.init_scan_state``)
copies ``prev_params`` instead of aliasing ``params``, because theta^{-1}
must survive the write of theta^1 into the donated theta^0 buffer.

These are regression tests for that guard: they pin the non-aliasing
property directly (buffer pointers, not values) and pin that donation is
a pure perf knob — donated and undonated runs are bit-identical.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import pytest

import exactness
from repro import opt
from repro.core import distributed, simulator
from repro.core.chb import FedOptConfig
from repro.data import paper_tasks

M = 4


def _ptrs(tree):
    return {x.unsafe_buffer_pointer()
            for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "unsafe_buffer_pointer")}


@pytest.fixture(scope="module")
def bundle():
    return paper_tasks.make_linear_regression(m=M, n_per=20, d=12, seed=3)


@pytest.mark.parametrize("backend", sorted(opt.BACKENDS))
def test_opt_init_prev_params_never_aliases_params(backend):
    """``OptState.prev_params`` buffers are disjoint from ``params`` at
    step 0 — the donated-theta^0 aliasing guard."""
    params = {"w": jnp.arange(12.0, dtype=jnp.float32),
              "b": jnp.ones((3,), jnp.float32)}
    o = opt.make("chb", 0.05, M, backend=backend)
    state = o.init(params)
    assert not (_ptrs(state.prev_params) & _ptrs(params))
    # and the values still agree: the guard is a copy, not a recompute
    exactness.leaves_equal(state.prev_params, params)


def test_distributed_init_scan_state_never_aliases_params():
    params = {"w": jnp.arange(8.0, dtype=jnp.float32)}
    cfg = FedOptConfig(alpha=0.05, num_workers=M)
    state = distributed.init_scan_state(cfg, params)
    assert not (_ptrs(state.prev_params) & _ptrs(params))


@pytest.mark.parametrize("backend", sorted(opt.BACKENDS))
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_simulator_donation_is_bit_identical(bundle, backend, quantize):
    """``run(donate=True)`` == ``run(donate=False)`` bit-for-bit; donation
    may only change buffer reuse, never a single rounding."""
    o = opt.make("chb", bundle.alpha_paper, M, quantize=quantize,
                 backend=backend)
    h_plain = simulator.run(o, bundle.task, 30)
    # a fresh task copy: donate=True invalidates its init_params buffers
    donated_task = bundle.task._replace(
        init_params=jax.tree_util.tree_map(jnp.copy,
                                           bundle.task.init_params))
    h_donated = simulator.run(o, donated_task, 30, donate=True)
    exactness.assert_bitwise(h_plain, h_donated)


def test_trainer_donation_flag_is_bit_identical():
    """The trainer's ``donate`` knob (default on) must not change the
    training trajectory — same losses, same uplink counts."""
    from repro.configs import get
    from repro.train import trainer

    cfg = get("chb-paper-lm-124m").reduced()
    losses = {}
    for donate in (True, False):
        tc = trainer.TrainConfig(algorithm="chb", num_workers=2,
                                 global_batch=4, seq_len=16, steps=6,
                                 log_every=2, donate=donate)
        _, state, hist = trainer.train(cfg, tc, verbose=False)
        losses[donate] = ([rec["loss"] for rec in hist],
                          int(state.comm.total_uplinks))
    assert losses[True] == losses[False]


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_fed_mesh_donation_is_bit_identical(bundle, quantize):
    """``run_mesh(donate=True)`` donates each shard's client bank into
    its round program; like the simulator knob it must be bit-neutral —
    including the copy guarding the post-quorum prev_params overwrite."""
    from repro.fed.mesh import MeshScenario, run_mesh
    o = opt.make("chb", bundle.alpha_paper, M, quantize=quantize)
    sc = MeshScenario(participation=0.75, loss_prob=0.2, quorum=0.6,
                      seed=9)
    plain = run_mesh(o, bundle.task, 12, scenario=sc)
    donated = run_mesh(o, bundle.task, 12, scenario=sc, donate=True)
    exactness.assert_bitwise(plain, donated)

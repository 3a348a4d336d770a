"""Pytree-aware dispatch onto the Pallas kernels + jit-compiled wrappers.

Two layers live here:

  * **Tree-level dispatch** (``tree_*``) — what the ``repro.opt`` pallas
    backend executes: leading-M-batched censor sqnorms, fused bank
    advances, the fused int8 + error-feedback sweep, and the eq.-(4)
    heavy-ball update, mapped over whole parameter pytrees. These are
    pure traceable functions (no ``jit`` of their own) so they inline
    into whatever program is being built — ``simulator.trajectory``'s
    scan, the sweep engine's ``lax.map`` partitions, ``core/distributed``
    strategies, or the ``repro.fed`` per-client closures.
  * **Jit-compiled single-tensor wrappers** (``censor_delta_sqnorm``,
    ``censor_select``, ``hb_param_update``, ``flash_attention_fwd``) —
    convenience entry points with a jnp fallback (``use_pallas=False``).

Hyperparameter contract: ``alpha``/``beta`` (and the censor's eps1, which
never reaches a kernel) are **traced scalar operands** everywhere — they
ride in SMEM blocks, not in the kernel closure, so sweeping a
hyperparameter grid reuses one compiled program. ``trace_counts`` records
how many times each dispatch function was traced (Python-side side effect:
it only ticks at trace time, never at execution time), which is how
``tests/test_kernels.py`` and ``benchmarks/kernel_roofline.py`` measure
retraces.

The interpret-vs-Mosaic decision lives in ``common.interpret_default`` and
is shared with direct kernel-module calls, so both entry points agree: on
CPU (this container) kernels run in interpret mode for validation; on a
real TPU both lower through Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import (censor, flash_attention, fused_step, hb_update, lowrank_ef,
               quantize_ef, ref, topk_pack)
from .common import interpret_default
from ..obs import compile_log

_interpret_default = interpret_default      # legacy alias (pre-backend name)


# ------------------------------------------------------- trace accounting
# The counters live in the process-wide ``repro.obs.compile_log`` under the
# "kernels" namespace; ``trace_counts`` is the *live* dict for that
# namespace (the same object the recorder updates), kept for the original
# API. ``obs.compile_log.snapshot()`` sees these ticks as "kernels/<name>"
# next to every other surface's counters.
trace_counts: dict[str, int] = compile_log.namespace("kernels")


def reset_trace_counts() -> None:
    """Zero the per-dispatch trace counters."""
    compile_log.reset("kernels")


def _traced(name: str) -> None:
    compile_log.record("kernels", name)


def _dispatch(fn):
    """Tree-dispatch wrapper: tick the compile log at trace time and wrap
    the kernel calls in a ``jax.named_scope`` so profiler traces (see
    ``repro.obs.profile``) attribute device time to the dispatch by name.
    The scope is HLO metadata only — numerics are untouched."""
    name = fn.__name__

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        _traced(name)
        with jax.named_scope(f"kernels/{name}"):
            return fn(*args, **kwargs)
    return wrapped


# ----------------------------------------------------- tree-level dispatch
@_dispatch
def tree_delta_sqnorms(grads, bank, *, tiles: bool = False,
                       hbm: bool = False, block_rows: int = 256,
                       interpret: bool | None = None) -> jax.Array:
    """(M,) per-worker ||g_m - ghat_m||^2 over a whole pytree.

    The eq.-(8) left-hand side, fused: one sweep per leaf over the stacked
    bank, no materialized delta tree. The subtraction dtype and the
    leaf-by-leaf f32 accumulation match ``core.censoring.delta_sqnorms``;
    *within* a leaf the tiled partial sums regroup the float additions,
    so values agree with the reference reduction to ulps, not bits (a
    censor decision landing exactly on the eq.-(8) threshold could
    therefore differ — see ``docs/kernels.md``). With ``tiles`` both
    trees hold ``(M, R, 128)`` tiles already (``common._pad_to_3d``), and
    ``hbm`` holds them in HBM (``common.in_hbm``).
    """
    leaf = functools.partial(censor.censor_delta_sqnorm_tiles, hbm=hbm) \
        if tiles else censor.censor_delta_sqnorm_batched
    leaves_g = jax.tree_util.tree_leaves(grads)
    leaves_h = jax.tree_util.tree_leaves(bank)
    acc = jnp.zeros((leaves_h[0].shape[0],), jnp.float32)
    for g, h in zip(leaves_g, leaves_h):
        acc = acc + leaf(g, h, block_rows=block_rows, interpret=interpret)
    return acc


@_dispatch
def tree_sqnorms(pending, *, block_rows: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """(M,) per-worker ||x_m||^2 of a materialized pending-delta pytree."""
    leaves = jax.tree_util.tree_leaves(pending)
    acc = jnp.zeros((leaves[0].shape[0],), jnp.float32)
    for x in leaves:
        acc = acc + censor.sqnorm_batched(x, block_rows=block_rows,
                                          interpret=interpret)
    return acc


@_dispatch
def tree_sqnorm_row(pending_row, *, block_rows: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """One worker's ||x||^2 (the ``repro.fed`` per-client entry point).

    Runs the batched kernel at M=1, so tile partials — and therefore the
    censor decision — are bit-identical to the batched step's per-worker
    slice.
    """
    leaves = jax.tree_util.tree_leaves(pending_row)
    acc = jnp.zeros((1,), jnp.float32)
    for x in leaves:
        acc = acc + censor.sqnorm_batched(x[None], block_rows=block_rows,
                                          interpret=interpret)
    return acc[0]


@_dispatch
def tree_censor_bank_advance(grads, bank, mask, *, tiles: bool = False,
                             hbm: bool = False, block_rows: int = 256,
                             interpret: bool | None = None):
    """Fused censor-select bank advance: ``ghat + mask * (g - ghat)``.

    With ``tiles`` both trees hold ``(M, R, 128)`` tiles and each leaf
    advances in its own buffer (the bank tile is aliased to the result);
    ``hbm`` holds them in HBM (``common.in_hbm``).
    """
    if tiles:
        return jax.tree_util.tree_map(
            lambda g, h: censor.censor_bank_advance_tiles(
                g, h, mask, block_rows=block_rows, in_place=True, hbm=hbm,
                interpret=interpret),
            grads, bank)
    return jax.tree_util.tree_map(
        lambda g, h: censor.censor_bank_advance(
            g, h, mask, block_rows=block_rows, interpret=interpret),
        grads, bank)


@_dispatch
def tree_bank_advance(bank, payload, mask, *, block_rows: int = 256,
                      interpret: bool | None = None):
    """Fused bank advance from an encoded payload: ``ghat + mask * q``."""
    return jax.tree_util.tree_map(
        lambda h, q: censor.bank_advance(
            h, q, mask, block_rows=block_rows, interpret=interpret),
        bank, payload)


@_dispatch
def tree_int8_roundtrip_ef(pending, err, mask, *, block_rows: int = 256,
                           interpret: bool | None = None):
    """Fused per-worker int8 round-trip + error-feedback over a pytree.

    Per leaf: a one-sweep abs-max reduction derives the per-worker scales
    (``where(amax > 0, amax/127, 1)``, exactly ``core/quantize``'s), then
    one fused sweep emits the dequantized payload and the next
    error-feedback leaf together. Returns ``(payload_tree, new_err_tree)``.
    """

    def one_leaf(p, e):
        amax = quantize_ef.absmax_batched(p, block_rows=block_rows,
                                          interpret=interpret)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        return quantize_ef.quantize_ef_batched(
            p, e, mask, scale, block_rows=block_rows, interpret=interpret)

    leaves_p, treedef = jax.tree_util.tree_flatten(pending)
    leaves_e = treedef.flatten_up_to(err)
    outs = [one_leaf(p, e) for p, e in zip(leaves_p, leaves_e)]
    payload = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_err = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return payload, new_err


@_dispatch
def tree_topk_pack_ef(pending, err, keep, mask, *, block_rows: int = 256,
                      interpret: bool | None = None):
    """Fused per-worker top-k select/pack + error-feedback over a pytree.

    ``keep`` holds the transport's 0/1 keep masks (exact host-graph
    ``lax.top_k`` selections); per leaf ONE fused sweep emits the sparse
    payload and the next error-feedback leaf together. Returns
    ``(payload_tree, new_err_tree)``.
    """
    leaves_p, treedef = jax.tree_util.tree_flatten(pending)
    leaves_e = treedef.flatten_up_to(err)
    leaves_k = treedef.flatten_up_to(keep)
    outs = [topk_pack.select_pack_ef_batched(
        p, e, kp, mask, block_rows=block_rows, interpret=interpret)
        for p, e, kp in zip(leaves_p, leaves_e, leaves_k)]
    payload = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_err = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return payload, new_err


@_dispatch
def tree_residual_ef(pending, payload, err, mask, *, block_rows: int = 256,
                     interpret: bool | None = None):
    """Fused masked error-feedback residual over a pytree.

    Per leaf ONE sweep computes ``mk*(pending - payload) + (1-mk)*err``
    (the low-rank transport's EF tail; its factor matmuls stay host-graph
    jnp). Returns the new error-feedback tree.
    """
    return jax.tree_util.tree_map(
        lambda p, q, e: lowrank_ef.residual_ef_batched(
            p, q, e, mask, block_rows=block_rows, interpret=interpret),
        pending, payload, err)


@_dispatch
def tree_fused_dense_step(grads, bank, params, prev_params, mask, alpha,
                          beta, *, block_rows: int = 256,
                          interpret: bool | None = None):
    """The post-``decide`` dense megakernel over a whole pytree.

    Per leaf ONE fused sweep performs the censor-select bank advance, the
    eq.-(5) worker-sum aggregation, and the eq.-(4) heavy-ball epilogue
    (``alpha``/``beta`` as traced SMEM operands). Returns
    ``(new_ghat, agg, new_params)`` — bitwise the staged
    ``tree_censor_bank_advance`` → ``tree_sum_leading`` →
    ``tree_hb_update`` composition, in a third of the HBM sweeps.
    """
    leaves_t, treedef = jax.tree_util.tree_flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_h = treedef.flatten_up_to(bank)
    leaves_p = treedef.flatten_up_to(prev_params)
    outs = [fused_step.fused_dense_step(
        g, h, t, tp, mask, alpha, beta, block_rows=block_rows,
        interpret=interpret)
        for g, h, t, tp in zip(leaves_g, leaves_h, leaves_t, leaves_p)]
    unflat = jax.tree_util.tree_unflatten
    return (unflat(treedef, [o[0] for o in outs]),
            unflat(treedef, [o[1] for o in outs]),
            unflat(treedef, [o[2] for o in outs]))


@_dispatch
def tree_int8_stats(grads, bank, err, *, block_rows: int = 256,
                    interpret: bool | None = None):
    """Per-worker eq.-(8) sqnorms + int8 scales, pending never materialized.

    One fused reduction sweep per leaf recomputes
    ``pending = (g - ghat) + err`` in-register and emits the sqnorm and
    abs-max tile partials together. Returns ``(dsq, scales)``: the (M,)
    f32 eq.-(8) left-hand side (leaf accumulation order identical to
    ``tree_sqnorms``) and a pytree of (M,) f32 per-leaf quantization
    scales (the staged ``where(amax > 0, amax/127, 1)`` expression).
    """
    leaves_g, treedef = jax.tree_util.tree_flatten(grads)
    leaves_h = treedef.flatten_up_to(bank)
    leaves_e = treedef.flatten_up_to(err)
    acc = jnp.zeros((leaves_h[0].shape[0],), jnp.float32)
    scales = []
    for g, h, e in zip(leaves_g, leaves_h, leaves_e):
        sq, amax = fused_step.int8_stats_batched(
            g, h, e, block_rows=block_rows, interpret=interpret)
        acc = acc + sq
        scales.append(jnp.where(amax > 0, amax / 127.0,
                                1.0).astype(jnp.float32))
    return acc, jax.tree_util.tree_unflatten(treedef, scales)


@_dispatch
def tree_fused_int8_step(grads, bank, err, params, prev_params, mask,
                         scales, alpha, beta, *, block_rows: int = 256,
                         interpret: bool | None = None):
    """The post-``decide`` int8+EF megakernel over a whole pytree.

    Per leaf ONE fused sweep recomputes the pending delta in-register,
    quantize-roundtrips it (the dequantized payload never touches HBM),
    blends the error-feedback bank, advances the stale bank, aggregates
    the workers, and applies eq. (4). ``scales`` is ``tree_int8_stats``'s
    per-leaf (M,) scale pytree. Returns
    ``(new_ghat, new_err, agg, new_params)`` — bitwise the staged
    ``tree_int8_roundtrip_ef`` → ``tree_bank_advance`` →
    ``tree_sum_leading`` → ``tree_hb_update`` composition.
    """
    leaves_t, treedef = jax.tree_util.tree_flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_h = treedef.flatten_up_to(bank)
    leaves_e = treedef.flatten_up_to(err)
    leaves_p = treedef.flatten_up_to(prev_params)
    leaves_s = treedef.flatten_up_to(scales)
    outs = [fused_step.fused_int8_step(
        g, h, e, t, tp, mask, s, alpha, beta, block_rows=block_rows,
        interpret=interpret)
        for g, h, e, t, tp, s in zip(leaves_g, leaves_h, leaves_e,
                                     leaves_t, leaves_p, leaves_s)]
    unflat = jax.tree_util.tree_unflatten
    return (unflat(treedef, [o[0] for o in outs]),
            unflat(treedef, [o[1] for o in outs]),
            unflat(treedef, [o[2] for o in outs]),
            unflat(treedef, [o[3] for o in outs]))


@_dispatch
def tree_hb_update(params, prev_params, agg, alpha, beta, *,
                   block_rows: int = 256, interpret: bool | None = None):
    """Fused eq.-(4) server update over a whole parameter pytree.

    ``alpha``/``beta`` may be traced scalars (SMEM operands — no retrace
    across a hyperparameter grid). Plain GD is ``beta = 0``, bit-identical
    to the reference ``GradientDescent`` stage by construction.
    """
    return jax.tree_util.tree_map(
        lambda t, tp, g: hb_update.hb_update(
            t, g, tp, alpha, beta, block_rows=block_rows,
            interpret=interpret),
        params, prev_params, agg)


# ------------------------------------------- jitted single-tensor wrappers
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def censor_delta_sqnorm(g, ghat, use_pallas: bool = True):
    if use_pallas:
        return censor.censor_delta_sqnorm(g, ghat)
    return ref.censor_delta_sqnorm(g, ghat)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def censor_select(g, ghat, transmit, use_pallas: bool = True):
    if use_pallas:
        return censor.censor_select(g, ghat, transmit)
    return ref.censor_select(g, ghat, transmit)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def hb_param_update(theta, nabla, theta_prev, alpha, beta,
                    use_pallas: bool = True):
    """Eq.-(4) update; ``alpha``/``beta`` are traced operands, so calling
    this across a hyperparameter grid compiles exactly once per shape."""
    if use_pallas:
        return hb_update.hb_update(theta, nabla, theta_prev, alpha, beta)
    return ref.hb_update(theta, nabla, theta_prev, alpha, beta)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "q_block",
                                    "kv_block", "use_pallas"))
def flash_attention_fwd(q, k, v, causal: bool = True, window=None,
                        q_block: int = 512, kv_block: int = 512,
                        use_pallas: bool = True):
    if use_pallas:
        return flash_attention.flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_block=q_block,
            kv_block=kv_block, interpret=interpret_default())
    return ref.flash_attention_fwd(q, k, v, causal=causal, window=window)

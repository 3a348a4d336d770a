"""One-sweep fused CHB step: the post-``decide`` megakernel.

The staged pallas path materializes every inter-stage intermediate of the
composed step (pending delta, quantized payload, advanced bank, worker
sum) as its own HBM round-trip. The kernels here collapse everything that
happens *after* the censor decision into ONE pass over the ``(M, n)``
bank, so a composed step becomes two sweeps total:

  sweep 1 (reduction): per-worker eq.-(8) sqnorms feeding
      ``censor.decide`` — ``censor.censor_delta_sqnorm_batched`` for the
      dense transport, or :func:`int8_stats_batched` (sqnorm + abs-max
      partials from an in-register pending recompute) for int8+EF;
  sweep 2 (elementwise): :func:`fused_dense_step` /
      :func:`fused_int8_step` — transport encode + error-feedback blend,
      bank advance, eq.-(5) worker-sum aggregation, and the eq.-(4)
      heavy-ball epilogue, per leaf, in one ``pallas_call``.

Bit-exactness contract (same as every kernel in this package): each fused
stage evaluates the staged path's exact expressions in the staged path's
dtypes. Two structural choices make that hold to the bit:

  * the whole worker axis rides in ONE ``(M, block, 128)`` VMEM block and
    the kernel aggregates with ``jnp.sum(·, axis=0)`` — the same reduce
    HLO the staged path's host-side ``tree_sum_leading`` lowers to (a
    sequential zero-init accumulator fold is NOT bitwise equal to XLA's
    axis-0 reduce grouping);
  * int8 never materializes the pending tree: both sweeps recompute
    ``pending = (g - ghat) + err`` in-register with the identical
    (deterministic, elementwise) expression, so the recomputed values are
    bitwise the staged path's materialized ones — and the dequantized
    payload never touches HBM at all.

``alpha``/``beta`` are traced SMEM operands (the ``baked-traced-hparam``
contract — one compile per shape across a whole hyperparameter grid).
The megakernels need the per-worker mask (+ int8 scale) as vectors over
the worker axis, and SMEM loads only scalars, so those ride lane-dense in
an ``(M, 1, 128)``/``(M, 2, 128)`` VMEM block (``common.lane_dense``).
``eps1`` is consumed by ``censor.decide`` between the sweeps and never
reaches a kernel. ``interpret=None`` resolves through
``common.interpret_default`` like every kernel in this package.

The module-level :func:`force_staged` context manager routes
``ComposedOptimizer`` back through the staged per-stage kernels at trace
time — the conformance suite and the roofline benchmark use it to compare
the two programs on identical inputs.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (_LANES, _pad_to_2d, _pad_to_3d, block_for,
                     compute_dtype, lane_dense, log_traffic,
                     resolve_interpret, tile_partials_spec)

__all__ = ["fused_dense_step", "fused_int8_step", "int8_stats_batched",
           "fusion_enabled", "force_staged"]


# ------------------------------------------------------- fused/staged toggle
_FUSION_ENABLED = True


def fusion_enabled() -> bool:
    """Whether ``ComposedOptimizer``'s pallas backend traces the megakernel.

    Consulted at *trace* time: flipping it affects programs traced after
    the flip, never already-compiled ones.
    """
    return _FUSION_ENABLED


@contextlib.contextmanager
def force_staged():
    """Trace the staged per-stage kernels instead of the fused megakernel.

    For A/B comparison only (conformance tests, the roofline benchmark's
    staged-vs-fused columns): both programs compute one run at f32/f64 —
    masks, counts, params and bank bit-identical, the diagnostic
    ``agg_grad_sqnorm`` within a few ulps (tests/exactness.py) — and the
    staged one just moves more bytes.
    """
    global _FUSION_ENABLED
    prev = _FUSION_ENABLED
    _FUSION_ENABLED = False
    try:
        yield
    finally:
        _FUSION_ENABLED = prev


def _hb_scalars(alpha, beta, dtype) -> jax.Array:
    """(1, 2) SMEM block of traced eq.-(4) scalars in the compute dtype."""
    acc = compute_dtype(dtype)
    return jnp.stack([jnp.asarray(alpha).astype(acc),
                      jnp.asarray(beta).astype(acc)]).reshape(1, 2)


# ------------------------------------------------------ dense megakernel
def _fused_dense_kernel(s_ref, mk_ref, g_ref, h_ref, t_ref, p_ref,
                        ng_ref, agg_ref, out_ref):
    # bank advance: the arithmetic mask form, matching
    # censor._censor_bank_advance_kernel per element
    h = h_ref[...]                                   # (M, block, 128)
    g = g_ref[...].astype(h.dtype)
    mask = mk_ref[...].astype(h.dtype)               # (M, 1, 128)
    ng = h + mask * (g - h)
    ng_ref[...] = ng
    # eq. (5): whole worker axis in-block, so this is the same axis-0
    # reduce HLO as the staged path's host-side tree_sum_leading
    agg_ref[...] = jnp.sum(ng, axis=0)
    # eq. (4) epilogue, matching hb_update._hb_kernel. agg is re-read
    # through the ref, not kept in-register: XLA's FMA-contraction
    # heuristic treats a reduce result differently from a loaded operand,
    # and the contraction of ``t - alpha*agg`` must round exactly like
    # the staged kernel's (whose nabla is a load) in every jit context.
    acc = s_ref.dtype
    alpha = s_ref[0, 0]
    beta = s_ref[0, 1]
    t = t_ref[...].astype(acc)
    p = p_ref[...].astype(acc)
    out_ref[...] = (t - alpha * agg_ref[...].astype(acc)
                    + beta * (t - p)).astype(out_ref.dtype)


def fused_dense_step(g: jax.Array, ghat: jax.Array, theta: jax.Array,
                     theta_prev: jax.Array, mask: jax.Array, alpha, beta, *,
                     block_rows: int = 256, interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Everything after ``decide`` for one dense leaf, in ONE sweep.

    Fuses ``censor.censor_bank_advance`` + the eq.-(5) worker sum + the
    eq.-(4) ``hb_update.hb_update`` epilogue: one read of ``(g, ghat,
    theta, theta_prev)``, one write of ``(new_ghat, agg, new_theta)`` —
    the staged path's intermediate reads of the advanced bank and the
    aggregate never happen.

    Args:
      g: (M, ...) fresh worker gradients.
      ghat: (M, ...) stale bank leaf (its dtype is the bank dtype).
      theta / theta_prev: the parameter leaf and its predecessor.
      mask: (M,) f32 transmit mask from the censor stage.
      alpha / beta: traced eq.-(4) scalars (SMEM operands).
    Returns:
      ``(new_ghat, agg, new_theta)`` with ``agg = sum_m new_ghat_m`` in
      the bank dtype (unpadded, so downstream ``tree_sqnorm`` sees the
      staged path's exact array).
    """
    assert g.shape == ghat.shape and mask.shape == (g.shape[0],)
    if ghat.size == 0:
        return ghat, jnp.sum(ghat, axis=0), theta
    m = g.shape[0]
    shape, n = theta.shape, math.prod(theta.shape)
    s = _hb_scalars(alpha, beta, theta.dtype)
    mk = lane_dense(mask[:, None])                    # (M, 1, 128)
    g3 = _pad_to_3d(g, block_rows)
    h3 = _pad_to_3d(ghat, block_rows)
    t2 = _pad_to_2d(theta, block_rows)
    p2 = _pad_to_2d(theta_prev, block_rows)
    block = block_for(g3, block_rows)
    nr = g3.shape[1] // block
    b3 = pl.BlockSpec((m, block, _LANES), lambda i: (0, i, 0))
    b2 = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        _fused_dense_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((m, 1, _LANES), lambda i: (0, 0, 0)),
            b3, b3, b2, b2,
        ],
        out_specs=[b3, b2, b2],
        out_shape=[jax.ShapeDtypeStruct(h3.shape, ghat.dtype),
                   jax.ShapeDtypeStruct(t2.shape, ghat.dtype),
                   jax.ShapeDtypeStruct(t2.shape, theta.dtype)],
        interpret=resolve_interpret(interpret),
    )(s, mk, g3, h3, t2, p2)
    ng3, agg2, out2 = log_traffic("fused_dense_step",
                                  (s, mk, g3, h3, t2, p2), outs)
    return (ng3.reshape(m, -1)[:, :n].reshape((m,) + shape),
            agg2.reshape(-1)[:n].reshape(shape),
            out2.reshape(-1)[:n].reshape(shape))


# ----------------------------------------------- int8 sweep 1: stats kernel
def _int8_stats_kernel(g_ref, h_ref, e_ref, sq_ref, am_ref):
    # pending recomputed in-register with the staged path's exact
    # expression: delta in the bank dtype, err cast onto it
    h = h_ref[...]
    pending = (g_ref[...].astype(h.dtype) - h) + e_ref[...].astype(h.dtype)
    x = pending.astype(jnp.float32)
    i = pl.program_id(1)
    sq_ref[0, 0, i] = jnp.sum(x * x)           # == censor._sqnorm_batched
    # == quantize_ef._absmax_kernel, widened exactly to the SMEM dtype
    am_ref[0, 0, i] = jnp.max(jnp.abs(pending)).astype(am_ref.dtype)


def int8_stats_batched(g: jax.Array, ghat: jax.Array, err: jax.Array, *,
                       block_rows: int = 256,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Per-worker eq.-(8) sqnorms AND abs-max of one int8+EF leaf, fused.

    The staged path materializes ``pending = (g - ghat) + err`` to HBM
    and then sweeps it twice more (``sqnorm_batched`` + ``absmax_batched``
    = 5 row-reads total); here ONE read of ``(g, ghat, err)`` emits both
    per-tile partial sets together, and pending is never written.

    Returns ``(sqnorms, amax)``: (M,) f32 sqnorms (tile partials bitwise
    equal to the staged/row kernels') and (M,) abs-max in the bank dtype
    (max is exactly associative, so padding and tiling cannot perturb it).
    """
    assert g.shape == ghat.shape == err.shape
    m = g.shape[0]
    if g.size == 0:
        return jnp.zeros((m,), jnp.float32), jnp.zeros((m,), ghat.dtype)
    g3 = _pad_to_3d(g, block_rows)
    h3 = _pad_to_3d(ghat, block_rows)
    e3 = _pad_to_3d(err, block_rows)
    block = block_for(g3, block_rows)
    nr = g3.shape[1] // block
    outs = pl.pallas_call(
        _int8_stats_kernel,
        grid=(m, nr),
        in_specs=[pl.BlockSpec((1, block, _LANES),
                               lambda w, i: (w, i, 0))] * 3,
        out_specs=[tile_partials_spec(nr)] * 2,
        out_shape=[jax.ShapeDtypeStruct((m, 1, nr), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1, nr),
                                        compute_dtype(ghat.dtype))],
        interpret=resolve_interpret(interpret),
    )(g3, h3, e3)
    sq, am = log_traffic("int8_stats_batched", (g3, h3, e3), outs)
    return (jnp.sum(sq[:, 0], axis=1),
            jnp.max(am[:, 0], axis=1).astype(ghat.dtype))


# ------------------------------------------------------- int8 megakernel
def _fused_int8_kernel(s_ref, sc_ref, g_ref, h_ref, e_ref, t_ref, p_ref,
                       ng_ref, ne_ref, agg_ref, out_ref):
    # pending recomputed in-register — bitwise the sweep-1 values (same
    # deterministic elementwise expression), never materialized to HBM
    h = h_ref[...]                                   # (M, block, 128)
    e = e_ref[...]
    pending = (g_ref[...].astype(h.dtype) - h) + e.astype(h.dtype)
    mk32 = sc_ref[:, 0:1, :]                         # (M, 1, 128) f32
    scale = sc_ref[:, 1:2, :]
    # int8 round-trip in f32, matching quantize_ef._quantize_ef_kernel;
    # the dequantized payload lives only in VMEM
    q32 = jnp.clip(jnp.round(pending.astype(jnp.float32) / scale),
                   -127, 127)
    payload = (q32 * scale).astype(pending.dtype)
    mk = mk32.astype(pending.dtype)
    ne_ref[...] = mk * (pending - payload) \
        + (1.0 - mk) * e.astype(pending.dtype)
    # bank advance from the payload, matching censor._bank_advance_kernel
    ng = h + mk32.astype(h.dtype) * payload.astype(h.dtype)
    ng_ref[...] = ng
    agg_ref[...] = jnp.sum(ng, axis=0)
    # eq. (4) epilogue; agg re-read through the ref so the contraction of
    # ``t - alpha*agg`` matches the staged kernel's loaded-operand form
    # in every jit context (see _fused_dense_kernel)
    acc = s_ref.dtype
    alpha = s_ref[0, 0]
    beta = s_ref[0, 1]
    t = t_ref[...].astype(acc)
    p = p_ref[...].astype(acc)
    out_ref[...] = (t - alpha * agg_ref[...].astype(acc)
                    + beta * (t - p)).astype(out_ref.dtype)


def fused_int8_step(g: jax.Array, ghat: jax.Array, err: jax.Array,
                    theta: jax.Array, theta_prev: jax.Array,
                    mask: jax.Array, scale: jax.Array, alpha, beta, *,
                    block_rows: int = 256, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Everything after ``decide`` for one int8+EF leaf, in ONE sweep.

    Fuses ``quantize_ef.quantize_ef_batched`` + ``censor.bank_advance`` +
    the eq.-(5) worker sum + the eq.-(4) epilogue. The pending delta and
    the dequantized payload exist only in registers/VMEM: one read of
    ``(g, ghat, err, theta, theta_prev)``, one write of ``(new_ghat,
    new_err, agg, new_theta)``.

    Args:
      g / ghat / err: (M, ...) gradients, stale bank, error-feedback bank.
      theta / theta_prev: the parameter leaf and its predecessor.
      mask: (M,) f32 transmit mask from the censor stage.
      scale: (M,) f32 per-worker quantization scales, derived from
        :func:`int8_stats_batched`'s abs-max via the staged
        ``where(amax > 0, amax/127, 1)`` expression (``ops.py`` does this).
      alpha / beta: traced eq.-(4) scalars (SMEM operands).
    Returns:
      ``(new_ghat, new_err, agg, new_theta)``, all unpadded.
    """
    assert g.shape == ghat.shape == err.shape
    assert mask.shape == (g.shape[0],) and scale.shape == (g.shape[0],)
    if ghat.size == 0:
        return (ghat, jnp.zeros(ghat.shape, ghat.dtype),
                jnp.sum(ghat, axis=0), theta)
    m = g.shape[0]
    shape, n = theta.shape, math.prod(theta.shape)
    s = _hb_scalars(alpha, beta, theta.dtype)
    sc = lane_dense(jnp.stack([mask, scale], axis=1))          # (M, 2, 128)
    g3 = _pad_to_3d(g, block_rows)
    h3 = _pad_to_3d(ghat, block_rows)
    e3 = _pad_to_3d(err, block_rows)
    t2 = _pad_to_2d(theta, block_rows)
    p2 = _pad_to_2d(theta_prev, block_rows)
    block = block_for(g3, block_rows)
    nr = g3.shape[1] // block
    b3 = pl.BlockSpec((m, block, _LANES), lambda i: (0, i, 0))
    b2 = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        _fused_int8_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((m, 2, _LANES), lambda i: (0, 0, 0)),
            b3, b3, b3, b2, b2,
        ],
        out_specs=[b3, b3, b2, b2],
        out_shape=[jax.ShapeDtypeStruct(h3.shape, ghat.dtype),
                   jax.ShapeDtypeStruct(h3.shape, ghat.dtype),
                   jax.ShapeDtypeStruct(t2.shape, ghat.dtype),
                   jax.ShapeDtypeStruct(t2.shape, theta.dtype)],
        interpret=resolve_interpret(interpret),
    )(s, sc, g3, h3, e3, t2, p2)
    ng3, ne3, agg2, out2 = log_traffic("fused_int8_step",
                                       (s, sc, g3, h3, e3, t2, p2), outs)
    up3 = lambda x3: x3.reshape(m, -1)[:, :n].reshape((m,) + shape)  # noqa: E731
    return (up3(ng3), up3(ne3),
            agg2.reshape(-1)[:n].reshape(shape),
            out2.reshape(-1)[:n].reshape(shape))

"""Fused int8 quantize + error-feedback sweep (the Int8Transport hot path).

The reference transport costs four parameter sweeps per step on top of the
censor test: abs-max reduction, quantize round-trip, error-feedback
update, bank advance. Here the round-trip and the error-feedback update
fuse into ONE sweep per leaf (``quantize_ef_batched``: two outputs, one
read of pending/err), fed by a one-sweep per-worker abs-max reduction
(``absmax_batched``). The bank advance reuses
``censor.bank_advance``.

Numerics replicate ``core/quantize.quantize_roundtrip`` exactly: the
abs-max runs in the payload dtype (max is exactly associative, so tile
partials cannot perturb it), the scale is derived host-graph-side with the
same ``where(amax > 0, amax/127, 1)`` expression, and the round-trip
``clip(round(x/scale)) * scale`` runs in f32 — so the pallas backend's
int8 trajectories are bit-identical to the reference backend's.

``interpret=None`` resolves through ``common.interpret_default`` like
every kernel in this package.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (_LANES, _pad_to_3d, block_for, compute_dtype,
                     log_traffic, resolve_interpret,
                     tile_partials_spec, worker_scalar_spec, worker_scalars)

__all__ = ["absmax_batched", "quantize_ef_batched"]


def _absmax_kernel(x_ref, out_ref):
    # the partial is stored widened to out_ref's dtype (SMEM holds 32-bit
    # scalars); widening is exact, so the max is unchanged
    out_ref[0, 0, pl.program_id(1)] = \
        jnp.max(jnp.abs(x_ref[...])).astype(out_ref.dtype)


def absmax_batched(x: jax.Array, *, block_rows: int = 256,
                   interpret: bool | None = None) -> jax.Array:
    """Per-worker ``max |x_m|`` of one (M, ...) leaf, in ``x.dtype``.

    Zero padding cannot raise a max of absolute values, and max is exactly
    associative, so the tiled partials equal the reference
    ``jnp.max(jnp.abs(x_m))`` bit-for-bit.
    """
    m = x.shape[0]
    if x.size == 0:
        return jnp.zeros((m,), x.dtype)
    x3 = _pad_to_3d(x, block_rows)
    block = block_for(x3, block_rows)
    nr = x3.shape[1] // block
    partials = pl.pallas_call(
        _absmax_kernel,
        grid=(m, nr),
        in_specs=[pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0))],
        out_specs=tile_partials_spec(nr),
        out_shape=jax.ShapeDtypeStruct((m, 1, nr), compute_dtype(x.dtype)),
        interpret=resolve_interpret(interpret),
    )(x3)
    partials = log_traffic("absmax_batched", (x3,), partials)
    return jnp.max(partials[:, 0], axis=1).astype(x.dtype)


def _quantize_ef_kernel(s_ref, p_ref, e_ref, q_ref, ne_ref):
    mask = s_ref[0, 0, 0]
    scale = s_ref[0, 0, 1]
    pending = p_ref[...]
    q32 = jnp.clip(jnp.round(pending.astype(jnp.float32) / scale),
                   -127, 127)
    payload = (q32 * scale).astype(q_ref.dtype)
    q_ref[...] = payload
    mk = mask.astype(pending.dtype)
    ne_ref[...] = mk * (pending - payload) \
        + (1.0 - mk) * e_ref[...].astype(pending.dtype)


def quantize_ef_batched(pending: jax.Array, err: jax.Array,
                        mask: jax.Array, scale: jax.Array, *,
                        block_rows: int = 256,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """One-sweep int8 round-trip + error-feedback update of one (M, ...)
    leaf.

    Args:
      pending: (M, ...) deltas with the error residual already folded in.
      err: (M, ...) current error-feedback bank leaf (any float dtype).
      mask: (M,) f32 transmit mask from the censor stage.
      scale: (M,) f32 per-worker quantization scales (from
        :func:`absmax_batched` via ``where(amax > 0, amax/127, 1)``).
    Returns:
      ``(payload, new_err)`` — the dequantized payload the receiver
      reconstructs (``pending.dtype``) and the next error-feedback leaf
      (transmitted workers keep the fresh residual ``pending - payload``,
      censored workers keep their old residual), both computed from one
      read of each input.
    """
    assert pending.shape == err.shape and mask.shape == (pending.shape[0],)
    if pending.size == 0:
        return pending, jnp.zeros(pending.shape, pending.dtype)
    shape, dtype = pending.shape, pending.dtype
    m = shape[0]
    p3 = _pad_to_3d(pending, block_rows)
    e3 = _pad_to_3d(err, block_rows)
    sc = worker_scalars(mask, scale)                       # (M, 1, 2)
    block = block_for(p3, block_rows)
    nr = p3.shape[1] // block
    payload, new_err = pl.pallas_call(
        _quantize_ef_kernel,
        grid=(m, nr),
        in_specs=[
            worker_scalar_spec(2),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(p3.shape, dtype),
                   jax.ShapeDtypeStruct(p3.shape, dtype)],
        interpret=resolve_interpret(interpret),
    )(sc, p3, e3)
    payload, new_err = log_traffic("quantize_ef_batched", (sc, p3, e3),
                                   (payload, new_err))
    n = math.prod(shape[1:])
    return (payload.reshape(m, -1)[:, :n].reshape(shape),
            new_err.reshape(m, -1)[:, :n].reshape(shape))

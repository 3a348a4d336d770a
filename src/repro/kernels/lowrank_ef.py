"""Fused error-feedback residual sweep (the LowRankTransport hot path).

The PowerSGD factor math (matmuls + Gram-Schmidt) lives in
``opt.transport`` as plain jnp shared verbatim by both backends — those
ops already run on the MXU and fusing them would buy nothing while
risking bit-drift. What the pallas backend fuses is the elementwise tail:
given the reconstruction ``payload = P @ Q'^T``, ONE sweep per leaf
computes the masked error-feedback blend
``mk*(pending - payload) + (1-mk)*err`` (``residual_ef_batched``) — one
read of pending/payload/err instead of the reference path's subtract +
blend sweeps.

Numerics replicate ``opt.transport._ef_blend`` exactly (same expression,
same dtypes), so the pallas composed step stays bit-identical to the
reference backend at f32/f64.

``interpret=None`` resolves through ``common.interpret_default`` like
every kernel in this package.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (_LANES, _pad_to_3d, block_for, log_traffic,
                     resolve_interpret, worker_scalar_spec, worker_scalars)

__all__ = ["residual_ef_batched", "residual_ef_row"]


def _residual_ef_kernel(s_ref, p_ref, q_ref, e_ref, ne_ref):
    mask = s_ref[0, 0, 0]
    pending = p_ref[...]
    mk = mask.astype(pending.dtype)
    ne_ref[...] = mk * (pending - q_ref[...].astype(pending.dtype)) \
        + (1.0 - mk) * e_ref[...].astype(pending.dtype)


def residual_ef_batched(pending: jax.Array, payload: jax.Array,
                        err: jax.Array, mask: jax.Array, *,
                        block_rows: int = 256,
                        interpret: bool | None = None) -> jax.Array:
    """One-sweep masked EF residual of one (M, ...) leaf.

    Args:
      pending: (M, ...) deltas with the error residual already folded in.
      payload: (M, ...) encoded reconstruction the receiver sees.
      err: (M, ...) current error-feedback bank leaf.
      mask: (M,) f32 transmit mask from the censor stage.
    Returns:
      The next error-feedback leaf: transmitted workers keep the fresh
      residual ``pending - payload``, censored workers keep their old
      residual.
    """
    assert pending.shape == payload.shape == err.shape
    assert mask.shape == (pending.shape[0],)
    if pending.size == 0:
        return jnp.zeros(pending.shape, pending.dtype)
    shape, dtype = pending.shape, pending.dtype
    m = shape[0]
    p3 = _pad_to_3d(pending, block_rows)
    q3 = _pad_to_3d(payload, block_rows)
    e3 = _pad_to_3d(err, block_rows)
    sc = worker_scalars(mask)                               # (M, 1, 1)
    block = block_for(p3, block_rows)
    nr = p3.shape[1] // block
    new_err = pl.pallas_call(
        _residual_ef_kernel,
        grid=(m, nr),
        in_specs=[
            worker_scalar_spec(1),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        out_shape=jax.ShapeDtypeStruct(p3.shape, dtype),
        interpret=resolve_interpret(interpret),
    )(sc, p3, q3, e3)
    new_err = log_traffic("residual_ef_batched", (sc, p3, q3, e3), new_err)
    n = math.prod(shape[1:])
    return new_err.reshape(m, -1)[:, :n].reshape(shape)


def residual_ef_row(pending: jax.Array, payload: jax.Array,
                    err: jax.Array, *, block_rows: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """One worker's EF residual (the ``repro.fed`` entry point).

    Runs the batched kernel at M=1 with the transmit mask pinned to 1, so
    the result is bit-identical to the batched step's worker slice.
    """
    return residual_ef_batched(
        pending[None], payload[None], err[None],
        jnp.ones((1,), jnp.float32),
        block_rows=block_rows, interpret=interpret)[0]

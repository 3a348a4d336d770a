"""Fused top-k select/pack + error-feedback sweep (TopKTransport hot path).

The transport's keep masks come from ``jax.lax.top_k`` on the host graph
(selection and the ones-scatter are exact integer/compare ops — batching
cannot perturb them), so the kernel's job is the remaining elementwise
work: select the kept entries into the payload and fold the dropped mass
into the error-feedback bank, in ONE sweep per leaf with two outputs
(``select_pack_ef_batched`` — one read of pending/err/keep).

Numerics replicate the reference ``TopKTransport.encode`` +
``_ef_blend`` exactly: the payload is a ``where`` select (NOT a multiply
— ``x * 0`` would turn negative zeros positive and break bit-parity with
the reference), and the EF blend is the shared
``mk*(pending - payload) + (1-mk)*err`` form. Because every payload entry
is either ``pending`` or ``0.0`` bit-for-bit, ``payload + new_err ==
pending`` holds *bitwise* after a transmit (the ``exact_residual``
contract the conformance suite pins).

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

__all__ = ["select_pack_ef_batched", "select_pack_ef_row"]


def _select_pack_ef_kernel(s_ref, p_ref, e_ref, k_ref, q_ref, ne_ref):
    mask = s_ref[0, 0, 0]
    pending = p_ref[...]
    payload = jnp.where(k_ref[...] != 0, pending, jnp.zeros_like(pending))
    q_ref[...] = payload
    mk = mask.astype(pending.dtype)
    ne_ref[...] = mk * (pending - payload) \
        + (1.0 - mk) * e_ref[...].astype(pending.dtype)


def select_pack_ef_batched(pending: jax.Array, err: jax.Array,
                           keep: jax.Array, mask: jax.Array, *,
                           block_rows: int = 256,
                           interpret: bool | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """One-sweep top-k select + error-feedback update of one (M, ...) leaf.

    Args:
      pending: (M, ...) deltas with the error residual already folded in.
      err: (M, ...) current error-feedback bank leaf (any float dtype).
      keep: (M, ...) 0/1 keep masks in ``pending.dtype`` (from
        ``opt.transport.tree_topk_keep`` — exact, so host-side).
      mask: (M,) f32 transmit mask from the censor stage.
    Returns:
      ``(payload, new_err)`` — the sparse payload the receiver
      reconstructs (kept entries verbatim, zeros elsewhere) and the next
      error-feedback leaf (transmitted workers keep the dropped entries,
      censored workers keep their old residual), from one read of each
      input.
    """
    assert pending.shape == err.shape == keep.shape
    assert mask.shape == (pending.shape[0],)
    if pending.size == 0:
        return pending, jnp.zeros(pending.shape, pending.dtype)
    shape, dtype = pending.shape, pending.dtype
    m = shape[0]
    p3 = _pad_to_3d(pending, block_rows)
    e3 = _pad_to_3d(err, block_rows)
    k3 = _pad_to_3d(keep, block_rows)
    sc = worker_scalars(mask)                               # (M, 1, 1)
    block = block_for(p3, block_rows)
    nr = p3.shape[1] // block
    payload, new_err = pl.pallas_call(
        _select_pack_ef_kernel,
        grid=(m, nr),
        in_specs=[
            worker_scalar_spec(1),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
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
    )(sc, p3, e3, k3)
    payload, new_err = log_traffic("select_pack_ef_batched",
                                   (sc, p3, e3, k3), (payload, new_err))
    n = math.prod(shape[1:])
    return (payload.reshape(m, -1)[:, :n].reshape(shape),
            new_err.reshape(m, -1)[:, :n].reshape(shape))


def select_pack_ef_row(pending: jax.Array, err: jax.Array,
                       keep: jax.Array, *, block_rows: int = 256,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """One worker's select/pack + EF sweep (the ``repro.fed`` entry point).

    Runs the batched kernel at M=1 with the transmit mask pinned to 1
    (the event runtime only applies feedback on delivered uploads), so the
    tile partials are bit-identical to the batched step's worker slice.
    """
    payload, new_err = select_pack_ef_batched(
        pending[None], err[None], keep[None], jnp.ones((1,), jnp.float32),
        block_rows=block_rows, interpret=interpret)
    return payload[0], new_err[0]

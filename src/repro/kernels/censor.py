"""Fused censoring kernels (the CHB hot spot added on top of a train step).

Naively, the eq.-(8) test + bank advance costs three HBM sweeps per
parameter tensor per worker: (1) delta = g - ghat, (2) ||delta||^2
reduction, (3) select ghat' = g or ghat. We fuse into single-sweep
kernels:

  censor_delta_sqnorm : one pass, emits per-tile partial sums of
                        ||g - ghat||^2 (f32 accumulation in VMEM)
  censor_select       : one pass, ghat' = transmit ? g : ghat

plus the leading-M batched variants the ``repro.opt`` pallas backend
dispatches through (see ``ops.py``): ``censor_delta_sqnorm_batched`` /
``sqnorm_batched`` (per-worker eq.-(8) partials over the stacked bank,
without ever materializing the delta tree) and ``censor_bank_advance`` /
``bank_advance`` (the fused bank advance ``ghat + mask * delta``, written
in the arithmetic mask form so it is bit-identical to the reference jnp
step). ``censor_delta_sqnorm_tiles`` / ``censor_bank_advance_tiles`` are
their cores on operands already in tile form, for a bank kept tiled
between calls (``fed.run_mesh``'s pallas dense route).

Tiles are (block_rows, 128) VMEM blocks — ``block_rows=256`` by default,
shrunk to the tensor's own row count for small tensors (``common.tile_rows``).
Per-worker masks and the transmit flag ride in SMEM scalar blocks, and the
reductions write their per-tile partials as SMEM scalars
(``common.worker_scalar_spec`` / ``common.tile_partials_spec``).

Kernels default to ``interpret=None``, resolved by
``common.interpret_default()``: the Pallas interpreter everywhere except a
real TPU backend, where they lower through Mosaic for the fused
single-sweep performance. Direct calls and the ``ops.py`` wrappers share
that rule, so neither entry point silently ships interpreter performance
on TPU. Numerics are identical either way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (_LANES, _pad_to_2d, _pad_to_3d, block_for, in_hbm,
                     log_traffic, resolve_interpret,
                     tile_partials_spec, worker_scalar_spec, worker_scalars)

__all__ = [
    "censor_delta_sqnorm", "censor_select",
    "censor_delta_sqnorm_batched", "censor_delta_sqnorm_tiles",
    "sqnorm_batched", "censor_bank_advance", "censor_bank_advance_tiles",
    "bank_advance",
]


# --------------------------------------------------- single-tensor kernels
def _delta_sqnorm_kernel(g_ref, h_ref, out_ref):
    d = g_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
    out_ref[0, pl.program_id(0)] = jnp.sum(d * d)


def censor_delta_sqnorm(g: jax.Array, ghat: jax.Array, *,
                        block_rows: int = 256,
                        interpret: bool | None = None) -> jax.Array:
    """|| g - ghat ||^2 via a tiled one-sweep Pallas reduction."""
    assert g.shape == ghat.shape
    if g.size == 0:
        return jnp.zeros((), jnp.float32)
    g2 = _pad_to_2d(g, block_rows)
    h2 = _pad_to_2d(ghat, block_rows)
    block = block_for(g2, block_rows)
    nr = g2.shape[0] // block
    partials = pl.pallas_call(
        _delta_sqnorm_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, nr), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, nr), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(g2, h2)
    partials = log_traffic("censor_delta_sqnorm", (g2, h2), partials)
    return jnp.sum(partials)


def _select_kernel(t_ref, g_ref, h_ref, out_ref):
    transmit = t_ref[0, 0] != 0
    g = g_ref[...].astype(out_ref.dtype)
    h = h_ref[...]
    out_ref[...] = jnp.where(transmit, g, h)


def censor_select(g: jax.Array, ghat: jax.Array, transmit: jax.Array, *,
                  block_rows: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """ghat' = transmit ? g : ghat — single fused sweep."""
    assert g.shape == ghat.shape
    orig_shape, orig_dtype = ghat.shape, ghat.dtype
    if ghat.size == 0:
        return ghat
    g2 = _pad_to_2d(g, block_rows)
    h2 = _pad_to_2d(ghat, block_rows)
    t = jnp.asarray(transmit, jnp.int32).reshape(1, 1)
    block = block_for(g2, block_rows)
    nr = g2.shape[0] // block
    out = pl.pallas_call(
        _select_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(h2.shape, orig_dtype),
        interpret=resolve_interpret(interpret),
    )(t, g2, h2)
    out = log_traffic("censor_select", (t, g2, h2), out)
    n = math.prod(orig_shape)
    return out.reshape(-1)[:n].reshape(orig_shape)


# ------------------------------------------------ leading-M batched kernels
def _delta_sqnorm_batched_kernel(g_ref, h_ref, out_ref):
    # subtraction runs in the bank dtype (matching the reference step's
    # ``g.astype(h.dtype) - h``), the square-sum accumulates in f32
    d = (g_ref[...].astype(h_ref.dtype) - h_ref[...]).astype(jnp.float32)
    out_ref[0, 0, pl.program_id(1)] = jnp.sum(d * d)


def censor_delta_sqnorm_batched(g: jax.Array, ghat: jax.Array, *,
                                block_rows: int = 256,
                                interpret: bool | None = None) -> jax.Array:
    """Per-worker ||g_m - ghat_m||^2 partials of one (M, ...) leaf.

    One fused sweep over the stacked bank: the delta tree is never
    materialized. Returns (M,) f32 — the leaf's contribution to the
    eq.-(8) left-hand side.
    """
    assert g.shape == ghat.shape
    m = g.shape[0]
    if g.size == 0:
        return jnp.zeros((m,), jnp.float32)
    return censor_delta_sqnorm_tiles(
        _pad_to_3d(g, block_rows), _pad_to_3d(ghat, block_rows),
        block_rows=block_rows, interpret=interpret)


def censor_delta_sqnorm_tiles(g3: jax.Array, h3: jax.Array, *,
                              block_rows: int = 256, hbm: bool = False,
                              interpret: bool | None = None) -> jax.Array:
    """:func:`censor_delta_sqnorm_batched` on operands already tiled as
    ``_pad_to_3d`` tiles them: ``(M, R, 128)``, zero-padded. Padding adds
    nothing to a tile's partial, so the result is the padded entry's.
    ``hbm`` holds both operands in HBM (``common.in_hbm``)."""
    assert g3.shape == h3.shape
    if hbm:
        g3, h3 = in_hbm(g3, interpret), in_hbm(h3, interpret)
    m = g3.shape[0]
    block = block_for(g3, block_rows)
    nr = g3.shape[1] // block
    partials = pl.pallas_call(
        _delta_sqnorm_batched_kernel,
        grid=(m, nr),
        in_specs=[
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_specs=tile_partials_spec(nr),
        out_shape=jax.ShapeDtypeStruct((m, 1, nr), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(g3, h3)
    partials = log_traffic("censor_delta_sqnorm_batched", (g3, h3), partials)
    return jnp.sum(partials[:, 0], axis=1)


def _sqnorm_batched_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[0, 0, pl.program_id(1)] = jnp.sum(x * x)


def sqnorm_batched(x: jax.Array, *, block_rows: int = 256,
                   interpret: bool | None = None) -> jax.Array:
    """Per-worker ||x_m||^2 of one (M, ...) leaf (f32 accumulation).

    The pending-delta variant of :func:`censor_delta_sqnorm_batched`, for
    transports that materialize the pending tree anyway (error feedback).
    Tile partials are identical to the fused variant's, so the fed
    runtime's row entry point (``M=1``) reproduces the batched step's
    per-worker values bit-for-bit.
    """
    m = x.shape[0]
    if x.size == 0:
        return jnp.zeros((m,), jnp.float32)
    x3 = _pad_to_3d(x, block_rows)
    block = block_for(x3, block_rows)
    nr = x3.shape[1] // block
    partials = pl.pallas_call(
        _sqnorm_batched_kernel,
        grid=(m, nr),
        in_specs=[pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0))],
        out_specs=tile_partials_spec(nr),
        out_shape=jax.ShapeDtypeStruct((m, 1, nr), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x3)
    partials = log_traffic("sqnorm_batched", (x3,), partials)
    return jnp.sum(partials[:, 0], axis=1)


def _censor_bank_advance_kernel(m_ref, g_ref, h_ref, out_ref):
    h = h_ref[...]
    g = g_ref[...].astype(h.dtype)
    mask = m_ref[0, 0, 0].astype(h.dtype)
    out_ref[...] = h + mask * (g - h)


def censor_bank_advance(g: jax.Array, ghat: jax.Array, mask: jax.Array, *,
                        block_rows: int = 256,
                        interpret: bool | None = None) -> jax.Array:
    """Fused censor-select bank advance of one (M, ...) leaf.

    ``ghat'_m = ghat_m + mask_m * (g_m - ghat_m)`` in one sweep — the
    arithmetic form of "transmitted workers replace their bank row",
    matching the reference step's ``h + bcast(mask) * delta`` expression
    bit-for-bit (a ``where``-select would NOT: ``h + (g - h) != g`` in
    floating point). ``mask`` is the censor's (M,) f32 transmit mask,
    delivered to the kernel as a per-worker SMEM scalar.
    """
    assert g.shape == ghat.shape and mask.shape == (g.shape[0],)
    if ghat.size == 0:
        return ghat
    shape = ghat.shape
    m = g.shape[0]
    out = censor_bank_advance_tiles(
        _pad_to_3d(g, block_rows), _pad_to_3d(ghat, block_rows), mask,
        block_rows=block_rows, interpret=interpret)
    n = math.prod(shape[1:])
    return out.reshape(m, -1)[:, :n].reshape(shape)


def censor_bank_advance_tiles(g3: jax.Array, h3: jax.Array,
                              mask: jax.Array, *, block_rows: int = 256,
                              in_place: bool = False, hbm: bool = False,
                              interpret: bool | None = None) -> jax.Array:
    """:func:`censor_bank_advance` on ``(M, R, 128)`` tiles, returning
    the advanced bank as tiles of the same shape (its zero padding stays
    zero). ``in_place`` aliases the output to ``h3``, so a bank donated
    to the enclosing program advances in its own buffer. ``hbm`` holds
    both tile operands and the result in HBM (``common.in_hbm``)."""
    assert g3.shape == h3.shape and mask.shape == (g3.shape[0],)
    out_shape = jax.ShapeDtypeStruct(h3.shape, h3.dtype)
    if hbm:
        g3, h3 = in_hbm(g3, interpret), in_hbm(h3, interpret)
        if not resolve_interpret(interpret):
            out_shape = pltpu.HBM(h3.shape, h3.dtype)
    m = g3.shape[0]
    mk = worker_scalars(mask)
    block = block_for(g3, block_rows)
    nr = g3.shape[1] // block
    out = pl.pallas_call(
        _censor_bank_advance_kernel,
        grid=(m, nr),
        in_specs=[
            worker_scalar_spec(1),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        out_shape=out_shape,
        input_output_aliases={2: 0} if in_place else {},
        interpret=resolve_interpret(interpret),
    )(mk, g3, h3)
    return log_traffic("censor_bank_advance", (mk, g3, h3), out)


def _bank_advance_kernel(m_ref, q_ref, h_ref, out_ref):
    h = h_ref[...]
    mask = m_ref[0, 0, 0].astype(h.dtype)
    out_ref[...] = h + mask * q_ref[...].astype(h.dtype)


def bank_advance(ghat: jax.Array, payload: jax.Array, mask: jax.Array, *,
                 block_rows: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """``ghat'_m = ghat_m + mask_m * payload_m`` in one fused sweep.

    The pre-encoded-payload variant of :func:`censor_bank_advance`, used
    when the transport materializes the payload anyway (quantization).
    """
    assert payload.shape == ghat.shape and mask.shape == (ghat.shape[0],)
    if ghat.size == 0:
        return ghat
    shape, dtype = ghat.shape, ghat.dtype
    m = ghat.shape[0]
    q3 = _pad_to_3d(payload, block_rows)
    h3 = _pad_to_3d(ghat, block_rows)
    mk = worker_scalars(mask)
    block = block_for(q3, block_rows)
    nr = q3.shape[1] // block
    out = pl.pallas_call(
        _bank_advance_kernel,
        grid=(m, nr),
        in_specs=[
            worker_scalar_spec(1),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
            pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, _LANES), lambda w, i: (w, i, 0)),
        out_shape=jax.ShapeDtypeStruct(h3.shape, dtype),
        interpret=resolve_interpret(interpret),
    )(mk, q3, h3)
    out = log_traffic("bank_advance", (mk, q3, h3), out)
    n = math.prod(shape[1:])
    return out.reshape(m, -1)[:, :n].reshape(shape)

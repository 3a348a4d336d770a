"""Shared plumbing for the CHB Pallas kernels.

Every kernel in this package sees parameter tensors through the same lens:
the leaf is flattened and zero-padded into ``(rows, 128)`` lane-aligned
tiles (``_pad_to_2d``), or — for leading-M stacked bank leaves — into
``(M, rows, 128)`` with each worker slice padded independently
(``_pad_to_3d``), so a row entry point (``repro.fed``'s per-client path)
and the batched entry point (the composed step) produce bit-identical
per-worker tile partials.

``interpret_default`` is the single source of truth for the
interpret-vs-Mosaic decision: every kernel module resolves
``interpret=None`` through it, so direct kernel calls and the ``ops.py``
jit wrappers always agree (on TPU both lower through Mosaic; anywhere else
both run the Pallas interpreter).

Mosaic (the TPU kernel compiler) accepts a block only when its last two
dimensions are divisible by (8, 128) or equal the array's own, and loads
only scalars from SMEM. The per-worker spec helpers below keep to both
rules; ``tests/test_tpu_compile.py`` compiles every kernel for a v5e.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def interpret_default() -> bool:
    """True everywhere except a real TPU backend (Mosaic lowering)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """Resolve a kernel's ``interpret=None`` default to the backend rule."""
    return interpret_default() if interpret is None else bool(interpret)


def in_hbm(x: jax.Array, interpret: bool | None = None) -> jax.Array:
    """``x`` held in HBM where a compiled kernel takes it as an operand.

    Left to itself the TPU compiler may stage a kernel operand of a few
    tens of MB in VMEM, and the fusion that makes it then carries the
    read the kernel's own pipeline would do. The interpreter has no
    memory spaces, so there ``x`` passes as it is.
    """
    if resolve_interpret(interpret):
        return x
    return pltpu.with_memory_space_constraint(x, pltpu.HBM)


def tile_rows(n: int, block_rows: int) -> tuple[int, int]:
    """(padded row count R, grid length nr) for ``n`` flat elements.

    Small tensors shrink the block to the tensor's own row count instead of
    padding up to ``block_rows`` — a d=20 paper tensor is one (1, 128)
    tile, not a (256, 128) one. The result depends only on ``n`` and
    ``block_rows``, so the row and batched entry points tile identically.
    """
    r_needed = max(1, math.ceil(n / _LANES))
    block = min(block_rows, r_needed)
    nr = math.ceil(r_needed / block)
    return nr * block, nr


def _pad_to_2d(x: jax.Array, block_rows: int) -> jax.Array:
    """Flatten to zero-padded (R, 128); R a multiple of the block rows."""
    flat = x.reshape(-1)
    r, _ = tile_rows(flat.shape[0], block_rows)
    return jnp.pad(flat, (0, r * _LANES - flat.shape[0])).reshape(r, _LANES)


def _pad_to_3d(x: jax.Array, block_rows: int = 256) -> jax.Array:
    """(M, ...) leaf to zero-padded (M, R, 128), each worker slice padded
    exactly as ``_pad_to_2d`` pads the slice alone."""
    m = x.shape[0]
    flat = x.reshape(m, -1)
    r, _ = tile_rows(flat.shape[1], block_rows)
    return jnp.pad(flat, ((0, 0), (0, r * _LANES - flat.shape[1]))
                   ).reshape(m, r, _LANES)


def untile(x2d: jax.Array, shape) -> jax.Array:
    """One worker's ``(R, 128)`` tiles back to a leaf of ``shape``,
    dropping the zero padding ``_pad_to_2d`` added."""
    return x2d.reshape(-1)[:math.prod(shape)].reshape(shape)


def block_for(x2d: jax.Array, block_rows: int) -> int:
    """The per-tile row count ``_pad_to_2d``/``_pad_to_3d`` used."""
    return min(block_rows, x2d.shape[-2])


def worker_scalars(*cols: jax.Array) -> jax.Array:
    """Stack (M,) per-worker scalars into the (M, 1, C) f32 SMEM operand
    that :func:`worker_scalar_spec` blocks."""
    return jnp.stack([c.astype(jnp.float32) for c in cols],
                     axis=-1)[:, None, :]


def worker_scalar_spec(width: int) -> pl.BlockSpec:
    """SMEM block of worker ``w``'s ``width`` scalars in a ``(w, i)`` grid.

    A (1, 1, width) block of an (M, 1, width) array: its last two
    dimensions equal the array's, which a (1, width) block of (M, width)
    would not. The kernel reads ``ref[0, 0, c]``.
    """
    return pl.BlockSpec((1, 1, width), lambda w, i: (w, 0, 0),
                        memory_space=pltpu.SMEM)


def tile_partials_spec(nr: int) -> pl.BlockSpec:
    """SMEM output of worker ``w``'s ``nr`` tile partials in a ``(w, i)``
    grid: a (1, 1, nr) block of an (M, 1, nr) array that stays resident
    across the row axis ``i``; grid step ``(w, i)`` writes
    ``ref[0, 0, i]``."""
    return pl.BlockSpec((1, 1, nr), lambda w, i: (w, 0, 0),
                        memory_space=pltpu.SMEM)


def lane_dense(cols: jax.Array) -> jax.Array:
    """(M, C) per-worker scalars broadcast to an (M, C, 128) f32 VMEM
    operand, for kernels that hold the whole worker axis in one block and
    need the scalars as vectors (SMEM loads only scalars)."""
    cols = cols.astype(jnp.float32)
    return jnp.broadcast_to(cols[:, :, None], cols.shape + (_LANES,))


def compute_dtype(dtype) -> jnp.dtype:
    """f32 accumulation for sub-f32 params, native precision otherwise.

    bf16/f16 params are upcast to f32 inside the kernels (the documented
    kernel contract, shared with the ``ref.py`` oracles); f32 and f64
    params compute in their own dtype — which is what makes the pallas
    backend bit-identical to the reference jnp step at those precisions.
    """
    return jnp.promote_types(dtype, jnp.float32)


# --------------------------------------------------- kernel traffic recorder
# XLA's ``cost_analysis()`` over-counts interpret-mode pallas calls: the
# interpreter emulates the grid at the HLO level (dynamic-slice copies of
# every block per grid step), so "bytes accessed" reflects the emulation
# machinery, not the kernel's HBM contract. The recorder below measures
# what Mosaic would move: the padded operand + result bytes of each
# ``pallas_call``, ticked at *trace* time by every kernel wrapper in this
# package. Trace the step exactly once inside the context for a
# per-execution figure (``benchmarks/kernel_roofline.py`` does).
_TRAFFIC_LOG: dict[str, float] | None = None


class track_kernel_bytes:
    """Context manager recording per-kernel HBM traffic at trace time.

    ``with track_kernel_bytes() as rec: jax.jit(step).lower(...)`` leaves
    ``rec.bytes`` holding ``{kernel_name: padded operand+result bytes}``
    summed over every pallas call traced inside the context, and
    ``rec.total()`` the grand total. Nestable; execution-time calls of an
    already-traced program tick nothing.
    """

    def __init__(self):
        self.bytes: dict[str, float] = {}

    def __enter__(self) -> "track_kernel_bytes":
        global _TRAFFIC_LOG
        self._prev = _TRAFFIC_LOG
        _TRAFFIC_LOG = self.bytes
        return self

    def __exit__(self, *exc):
        global _TRAFFIC_LOG
        _TRAFFIC_LOG = self._prev
        return False

    def total(self) -> float:
        return float(sum(self.bytes.values()))


def log_traffic(name: str, operands, results):
    """Tick the active traffic log with one pallas call's HBM bytes.

    Pass-through: returns ``results`` unchanged so kernel wrappers can
    wrap their ``pallas_call`` invocation in one line. Counts every
    operand and result leaf at its padded device size (SMEM scalar blocks
    included — they are negligible but really are transferred).
    """
    if _TRAFFIC_LOG is not None:
        leaves = jax.tree_util.tree_leaves((operands, results))
        nbytes = float(sum(x.size * x.dtype.itemsize for x in leaves))
        _TRAFFIC_LOG[name] = _TRAFFIC_LOG.get(name, 0.0) + nbytes
    return results

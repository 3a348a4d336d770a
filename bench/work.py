"""The yardstick's arithmetic that no configuration owns: the least time a
piece of work takes at the chip's peaks, and the bytes each Pallas kernel's
call must move, counted from shapes.

What a configuration's round or step requires is counted in its own
``bench/configs/<config>.work.py``. Both count what the work needs, not
what the program happens to do, so a program that does less redundant work
reads closer to the roofline and one that does more reads further from it.
"""
from __future__ import annotations

import math


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     chips: int) -> float:
    """The least time the work takes on ``chips`` chips at their peaks."""
    return max(flops / (chips * peaks["flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))


# ---------------------------------------------------------------- kernels
def _numel(shape) -> int:
    return math.prod(shape)


def _nbytes(arrays) -> int:
    return sum(_numel(s) * b for s, b in arrays)


def kernel_bytes(name: str, operands: list, results: list) -> int:
    """Bytes a Pallas kernel's call must move, from its operand and result
    shapes as ``(shape, itemsize)`` pairs. A kernel not listed here is an
    error: its traffic has to be stated before its roofline is read."""
    if name not in KERNEL_BYTES:
        raise KeyError(f"no byte count for kernel {name!r}; add it to "
                       "bench/work.py KERNEL_BYTES")
    return KERNEL_BYTES[name](operands, results)


def _stream(operands, results) -> int:
    """Read every operand once and write every result once."""
    return _nbytes(operands) + _nbytes(results)


# The Pallas kernels of the fed cells' route, by the ``kernels/<name>``
# scope that ``repro.kernels.ops`` puts them under. Each is one streaming
# sweep: every input block is read once and every output block written once
# (scalar operands and tile partials included, which are negligible).
KERNEL_BYTES = {
    "tree_delta_sqnorms": _stream,
    "tree_censor_bank_advance": _stream,
    "tree_hb_update": _stream,
}

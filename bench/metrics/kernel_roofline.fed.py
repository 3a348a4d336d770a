"""Pallas kernels' share of their HBM roofline in the traced window.

For every device event of a Pallas (Mosaic) kernel: the bytes its call must
move, from its operand and result shapes (bench/work.py KERNEL_BYTES), over
the HBM bandwidth; summed, and divided by the events' summed device time.
A kernel that bench/work.py does not know fails the traced run.
"""
from bench import harness, tracing, work


def read(ctx, outcome, trace):
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    planes = set(trace.device_planes()[:len(ctx.devices)])
    need_s = busy_s = 0.0
    for e in trace.ops():
        if e.plane not in planes:
            continue
        call = tracing.kernel_call(e)
        if call is None:
            continue
        name, operands, results = call
        need_s += work.kernel_bytes(name, operands, results) \
            / peaks["hbm_bytes_per_s"]
        busy_s += e.dur_ns * 1e-9     # whole calls: bytes count whole
    if busy_s == 0.0:
        return None
    return 100.0 * need_s / busy_s

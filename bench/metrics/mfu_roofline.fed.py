"""The least time the traced window's rounds require, over the window.

The required work is counted from shapes by bench/work.py: each
participant's samples read once, its gradient, its bank row read and
written once. The least time is the larger of FLOPs over the chips' peak
and bytes over their HBM bandwidth.
"""
from bench import harness, work


def read(ctx, outcome, trace):
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    least = work.roofline_seconds(outcome.work["flops"],
                                  outcome.work["bytes"], peaks,
                                  outcome.work["chips"])
    return 100.0 * least / trace.window_s

"""Model FLOPs utilisation of the trainer's window: the FLOPs the tokens
require (bench/work.py, no recompute) over the traced window, as a share
of the chips' peak."""
from bench import harness


def read(ctx, outcome, trace):
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    flops = outcome.work["flops_per_token"] * outcome.work["tokens"]
    return 100.0 * flops / (trace.window_s * len(ctx.devices)
                            * peaks["flops_per_s"])

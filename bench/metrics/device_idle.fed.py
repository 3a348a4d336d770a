"""Share of the traced window in which no operation ran on the chips the
fed cell uses (mean over chips), under fed.mesh's host round loop."""


def read(ctx, outcome, trace):
    chips = len(ctx.devices)
    return 100.0 * (1.0 - trace.mean_busy_s(chips) / trace.window_s)

"""Device time of the client fold's all-reduce per round, mean over chips."""


def read(ctx, outcome, trace):
    chips = len(ctx.devices)
    secs = trace.op_seconds(lambda e: "all-reduce" in e.name.lower(), chips)
    if secs == 0.0:
        return None
    return 1e3 * secs / chips / outcome.work["rounds"]

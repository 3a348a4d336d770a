"""The first chip's idle time under the window's ``train/data`` spans (the
host making the next batch while the chip waits for it), per step, in
milliseconds."""
from bench import spans


def read(ctx, outcome, trace):
    steps = spans.find(trace, "train/step")
    if not steps:
        return None
    return 1e-6 * spans.idle_ns(trace, spans.find(trace, "train/data")) \
        / len(steps)

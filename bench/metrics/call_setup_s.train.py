"""The window call's set-up: from the start of its ``train`` span to the end
of its first ``train/dispatch``, which traces, lowers and loads the step
program, in seconds."""
from bench import spans


def read(ctx, outcome, trace):
    return spans.call_setup_s(trace, "train", "train/dispatch")

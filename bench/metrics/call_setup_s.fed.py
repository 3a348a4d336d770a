"""The window call's set-up: from the start of its ``run_mesh`` span to the
end of its first ``run_mesh/dispatch``, which traces, lowers and loads the
shard and server programs, in seconds."""
from bench import spans


def read(ctx, outcome, trace):
    return spans.call_setup_s(trace, "run_mesh", "run_mesh/dispatch")

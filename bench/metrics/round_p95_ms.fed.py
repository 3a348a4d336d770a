"""The 95th percentile (nearest rank) of the window's ``run_mesh/round``
span lengths, in milliseconds."""
from bench import spans


def read(ctx, outcome, trace):
    rounds = spans.find(trace, "run_mesh/round")
    if not rounds:
        return None
    return 1e-6 * spans.percentile([r.dur_ns for r in rounds], 95)

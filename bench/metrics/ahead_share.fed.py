"""Share of the window's ``run_mesh/sync`` spans that opened while the round
they read was still running (their stat ``ahead`` is 1): the chips then had
the next round queued while the host waited, so the device set the pace.
A program whose syncs carry no ``ahead`` stat gives nothing."""
from bench import spans


def read(ctx, outcome, trace):
    ahead = [s.stats["ahead"] for s in spans.find(trace, "run_mesh/sync")
             if "ahead" in s.stats]
    if not ahead:
        return None
    return 100.0 * sum(ahead) / len(ahead)

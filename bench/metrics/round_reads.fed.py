"""Reads back to the host a federated round makes: the mean ``reads`` stat of
the window's ``run_mesh/sync`` spans (one per array read)."""
from bench import spans


def read(ctx, outcome, trace):
    reads = [s.stats["reads"] for s in spans.find(trace, "run_mesh/sync")
             if "reads" in s.stats]
    if not reads:
        return None
    return sum(reads) / len(reads)

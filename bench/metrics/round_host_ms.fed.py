"""Host time of a federated round while the chips wait for it: the mean over
the window's ``run_mesh/round`` spans of their time not under
``run_mesh/sync`` (dispatching the shard and server programs, the
bookkeeping, the loop itself), in milliseconds."""
from bench import spans


def read(ctx, outcome, trace):
    rounds = spans.find(trace, "run_mesh/round")
    if not rounds:
        return None
    host = spans.self_ns(rounds, spans.find(trace, "run_mesh/sync"))
    return 1e-6 * sum(host) / len(host)

"""dispatch_gap_ms (ms): device idle at a round boundary, between the last
op of one execution of the round program and the first op of the next,
mean over the boundaries in the trace (the engine's dispatch, one round
per call)."""
from fedbench import trace


def read(ctx):
    gaps = trace.round_boundary_gaps(ctx["trace"])
    if not gaps:
        return None
    return 1e3 * sum(g for g, _ in gaps) / len(gaps)

"""unscoped.ms (ms): device self time per round of the ops under no phase
of the round (the compiler's layout copies, loop bookkeeping, a sort's
index iota), summed over the traced rounds and divided by their number
(``fedbench.phases``). With the phases it adds up to the busy time."""
from fedbench import phases


def read(ctx):
    return phases.phase_ms_per_round(ctx, phases.UNSCOPED)

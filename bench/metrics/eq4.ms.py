"""eq4.ms (ms): device self time per round of the internal sync (Eq. 4:
mean or coordinate median, quarantine, rollback), the ops whose compiled
``op_name`` has ``fedgs.eq4`` as its innermost phase, summed over the
traced rounds and divided by their number (``fedbench.phases``)."""
from fedbench import phases


def read(ctx):
    return phases.phase_ms_per_round(ctx, "fedgs.eq4")

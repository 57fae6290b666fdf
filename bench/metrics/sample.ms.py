"""sample.ms (ms): device self time per round of the device sampler, the
ops whose compiled ``op_name`` has ``fedgs.sample`` as its innermost
phase, summed over the traced rounds and divided by their number
(``fedbench.phases``)."""
from fedbench import phases


def read(ctx):
    return phases.phase_ms_per_round(ctx, "fedgs.sample")

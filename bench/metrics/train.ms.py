"""train.ms (ms): device self time per round of the model's forward and
backward, the ops whose compiled ``op_name`` has ``fedgs.train`` as its
innermost phase, summed over the traced rounds and divided by their
number (``fedbench.phases``)."""
from fedbench import phases


def read(ctx):
    return phases.phase_ms_per_round(ctx, "fedgs.train")

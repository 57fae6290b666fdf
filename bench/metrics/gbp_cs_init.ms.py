"""gbp_cs_init.ms (ms): device time per round of GBP-CS's Moore-Penrose
initializer (Eq. 14, ``pinv(A) y`` for every factory each iteration): the
TPU linear-algebra custom calls its SVD lowers to, named below by their
custom-call targets, summed over the traced rounds and divided by their
number."""
from fedbench import trace

NAMES = tuple(f'custom_call_target="{t}"' for t in (
    "EighTpu", "QrDecompositionBlock", "CompactWyHelper", "Cholesky",
    "InvertDiagBlocksLowerTriangular"))


def read(ctx):
    t = trace.op_time_s(ctx["trace"], NAMES)
    return 1e3 * t / ctx["rounds"] if t > 0 else None

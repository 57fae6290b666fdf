"""idle_share (%): the traced window's share in which no op ran on the
device, 1 - union of the device-op intervals / window, mean over chips."""
from fedbench import trace


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * trace.idle_share(tr) if tr.ops else None

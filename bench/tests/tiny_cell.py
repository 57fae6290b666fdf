"""The benchmark's cells at a size a CPU test run can hold."""
import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from fedbench import harness, spec  # noqa: E402

WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2_147_483_659


def tiny(workload: str) -> dict:
    """The cell at toy sizes: the toy model and flags of its counts
    module (``SMOKE``), 2 factories of 6 devices, 3 seated, 3 iterations
    of 4 samples; drift epochs and availability dwell shortened so both
    act inside the two checked rounds."""
    cell = spec.cell(workload)
    c = copy.deepcopy(cell["config"])
    smoke = copy.deepcopy(cell["counts"].SMOKE)
    c["model"] = smoke["model"]
    c["deployment"].update(groups=2, devices_per_group=6, selected=3,
                           presampled=1, iters=3, batch_size=4, lr=0.05)
    c["flags"] = list(c.get("flags", [])) + smoke["flags"]
    cell["config"] = c
    t = copy.deepcopy(cell["traffic"])
    if "drift" in t:
        t["drift"]["period"] = 2
    if "avail" in t:
        t["avail"].update(up_prob=0.6, dwell=2)
    cell["traffic"] = t
    return cell


def measure(cell: dict) -> dict:
    """One run of ``cell`` on the CPU, without the look for a chip."""
    return harness.measure(cell, SEED, 0.5, False, time.perf_counter(),
                           require_tpu=False)

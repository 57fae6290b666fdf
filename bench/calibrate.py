#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, many seeds in
one process (no measured window: training's readings need none).

  python3 bench/calibrate.py --workload paper.static --seeds 101-112 \\
      --control-seeds 101-103

For every seed: the program's set-up rounds against the plain reference
(the lower readings). For every control seed also: the reference computed
in bfloat16 put in the program's place (the control), and the reference
with half of each member's batch left out (a planted fault), each against
the float32 reference. One JSON line per reading on stdout.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fedbench import compare, harness, spec  # noqa: E402


def _seeds(text: str) -> list[int]:
    if not text:
        return []
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control-seeds", default="", help="first-last")
    args = ap.parse_args()
    cell = spec.cell(args.workload)
    import jax
    harness.device_info(cell["workload"]["chips"], require_tpu=True)
    harness.enable_compile_cache()
    rounds = cell["config"]["check_rounds"]
    controls = set(_seeds(args.control_seeds))
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        run = harness.start_program(cell, seed)
        prog = (run.p0, run.params, run.records)
        del run
        gc.collect()
        jax.clear_caches()
        t1 = time.perf_counter()
        ref = harness.reference_rounds(cell, seed, rounds)
        t2 = time.perf_counter()
        out = {"seed": seed, "kind": "program",
               "readings": compare.readings(*prog, ref[0], ref[2], ref[1]),
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "update1_leaves": [compare.leaf_norms(prog[1][0], prog[0]),
                                  compare.leaf_norms(ref[2][0], ref[0])],
               "records": prog[2], "reference_records": ref[1]}
        print(json.dumps(out), flush=True)
        if seed not in controls:
            continue
        for kind, kw in (("control", {"control": True}),
                         ("half_batch", {"half_batch": True})):
            alt = harness.reference_rounds(cell, seed, rounds, **kw)
            print(json.dumps({
                "seed": seed, "kind": kind,
                "readings": compare.readings(alt[0], alt[2], alt[1],
                                             ref[0], ref[2], ref[1])}),
                flush=True)
            jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, measured window, check against the plain
reference, one result line.

The system under test is the fused FedGS experiment exactly as
``launch/train.py --engine fused`` builds it (``parse_args``,
``build_world``, ``build_fedgs_experiment``), driven one round per dispatch
through the engine's own compiled chunk dispatch with on-device eval every
round. One compiled dispatch serves the set-up rounds and the window, so
nothing compiles inside the window.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import compare, phases, spec, trace as trace_lib, yardstick

TRACED_ROUNDS = 2          # a round boundary falls inside the trace


class NoChip(RuntimeError):
    pass


def _program_on_path() -> None:
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache``), with every program cached, so a
    second run of a cell compiles nothing it compiled before."""
    import jax
    _program_on_path()
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


COMPILES = collections.Counter()
_CACHE_EVENTS = {"/jax/compilation_cache/cache_misses": "programs_compiled",
                 "/jax/compilation_cache/cache_hits": "programs_from_cache"}


@functools.cache
def count_compiles() -> None:
    """From now on, count in ``COMPILES`` the programs compiled (each is
    written to the persistent cache) and loaded from that cache, and the
    seconds spent compiling or loading them, from JAX's monitoring
    events."""
    import jax

    def event(name, **_):
        if name in _CACHE_EVENTS:
            COMPILES[_CACHE_EVENTS[name]] += 1

    def duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES["compile_or_load_s"] += secs

    jax.monitoring.register_event_listener(event)
    jax.monitoring.register_event_duration_secs_listener(duration)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _host(tree):
    import jax
    import numpy as np
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def _record(rec) -> dict:
    return {k: v for k, v in rec._asdict().items()
            if isinstance(v, float) or v is None}


@dataclasses.dataclass
class ProgramRun:
    """The compiled round dispatch with its state, after the set-up
    rounds; ``params`` and ``records`` are those rounds' outputs."""
    exp: object
    dispatch: object
    state: object
    next_round: int
    p0: dict
    params: list
    records: list
    samples_per_round: int

    def round(self, annotate: bool = False):
        """Dispatch the next round and wait for its metrics, as the
        engine's own loop does; returns its record. ``annotate`` wraps the
        dispatch and the wait in ``bench.dispatch`` / ``bench.fetch``
        profiler spans."""
        import contextlib

        import jax
        import jax.numpy as jnp
        from repro.core import engine
        span = jax.profiler.TraceAnnotation if annotate else \
            (lambda name: contextlib.nullcontext())
        r = self.next_round
        with span("bench.dispatch"):
            self.state, met = self.dispatch(
                self.state, r + jnp.arange(1, dtype=jnp.int32))
        with span("bench.fetch"):
            rec = engine.records_from_metrics(r, met,
                                              strategy=self.exp.name)[0]
        self.next_round += 1
        return rec

    def global_params(self) -> dict:
        return _host(self.exp.params_fn(self.state))


def start_program(cell: dict, seed: int) -> ProgramRun:
    """Build the experiment, compile its dispatch and run the set-up
    rounds (the first one compiles), keeping each round's global
    parameters for the check."""
    import jax
    import jax.numpy as jnp
    _program_on_path()
    from repro.core import engine
    from repro.launch import train

    config = cell["config"]
    args = train.parse_args(spec.train_flags(config, cell["traffic"], seed))
    world = train.build_world(args)
    n_par = sum(leaf.size for leaf in jax.tree.leaves(world.params))
    want = cell["counts"].param_count(config)
    if n_par != want:
        raise ValueError(f"the program's model has {n_par} parameters, the "
                         f"configuration's counts ({config['counts']}) "
                         f"{want}")
    exp = train.build_fedgs_experiment(args, world, None)
    run = ProgramRun(
        exp=exp, dispatch=engine._make_chunk_fn(exp, 1, 1),
        state=jax.tree.map(lambda a: jnp.array(a, copy=True), exp.init_state),
        next_round=0, p0=_host(world.params), params=[], records=[],
        samples_per_round=yardstick.samples_per_round(config["deployment"]))
    for _ in range(config["check_rounds"]):
        run.records.append(_record(run.round()))
        run.params.append(run.global_params())
    return run


def reference_rounds(cell: dict, seed: int, rounds: int, *,
                     control: bool = False, half_batch: bool = False):
    """The plain reference from the seed: (initial params, per-round
    records, per-round global params). ``control`` computes it in
    bfloat16; otherwise under full float32 matmul precision."""
    import jax
    import jax.numpy as jnp
    mod = spec.load_module(spec.reference_path(cell["config"]),
                           "fedbench_reference")
    ref = mod.Reference(cell["config"], cell["traffic"], seed,
                        dtype=jnp.bfloat16 if control else jnp.float32,
                        half_batch=half_batch)
    with jax.default_matmul_precision("float32" if not control
                                      else "bfloat16"):
        recs, params = ref.run(rounds)
    return ref.initial_params(), recs, params


def _finite(rec: dict) -> bool:
    return all(rec.get(k) is not None and math.isfinite(rec[k])
               for k in ("loss", "test_loss", "test_accuracy"))


def round_text(run: ProgramRun) -> str:
    """The compiled round's HLO text, with each instruction's ``op_name``
    (the persistent cache holds the program, so this loads it)."""
    import jax.numpy as jnp
    return run.dispatch.lower(
        run.state, run.next_round + jnp.arange(1, dtype=jnp.int32)
    ).compile().as_text()


def reader_context(cell: dict, tr, hlo_text: str, device_kind: str) -> dict:
    """What every per-layer reader is given: the trace and the number of
    rounds in it; the compiled round's instruction name -> ``op_name``
    path (``op_names``) and -> phase (``scopes``); the cell's
    configuration, traffic and counts module; the chip's peaks."""
    return {"trace": tr, "rounds": TRACED_ROUNDS,
            "op_names": phases.op_names(hlo_text),
            "scopes": phases.op_scopes(hlo_text),
            "config": cell["config"], "traffic": cell["traffic"],
            "counts": cell["counts"], "peak": yardstick.peak(device_kind)}


def read_per_layer(cell: dict, ctx: dict) -> dict:
    """name -> {"value", "unit"} of each of the cell's per-layer metrics
    whose reader finds something to read."""
    out = {}
    for m in cell["per_layer"]:
        reader = spec.load_module(spec.metric_path(m["name"]),
                                  "metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(cell: dict, seed: int, seconds: float, traced: bool, t_start:
            float, *, require_tpu: bool = True) -> dict:
    """One run; returns the result object (its ``checks`` key last)."""
    import jax
    import numpy as np

    chips = cell["workload"]["chips"]
    device = device_info(chips, require_tpu)
    enable_compile_cache()
    count_compiles()
    before = dict(COMPILES)
    run = start_program(cell, seed)
    setup_s = time.perf_counter() - t_start
    setup = {k: COMPILES[k] - before.get(k, 0) for k in
             ("programs_compiled", "programs_from_cache", "compile_or_load_s")}

    window_recs, tr = [], None
    if traced:
        hlo_text = round_text(run)
        tmp = tempfile.mkdtemp(prefix="fedbench-trace-")
        try:
            jax.profiler.start_trace(tmp)
            for _ in range(TRACED_ROUNDS):
                window_recs.append(_record(run.round(annotate=True)))
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(run.state)
            jax.profiler.stop_trace()
            tr = trace_lib.load(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        window_s = None
    else:
        t0 = time.perf_counter()
        while True:
            window_recs.append(_record(run.round()))
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(run.state)
        window_s = time.perf_counter() - t0

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if "peak_bytes_in_use" in s]
    device["memory_peak_bytes"] = max(peaks) if peaks else None
    final_ok = all(bool(np.all(np.isfinite(a)))
                   for a in jax.tree.leaves(run.global_params()))
    attempted = len(window_recs)
    failed = sum(not _finite(r) for r in window_recs)

    metrics, phase_ms = {}, None
    if traced:
        if tr.ops:
            device["busy_s"] = trace_lib.busy_s(tr)
            device["window_s"] = tr.window_s
            ctx = reader_context(cell, tr, hlo_text, device["kind"])
            metrics = read_per_layer(cell, ctx)
            phase_ms = {k: 1e3 * v / TRACED_ROUNDS for k, v in
                        phases.phase_times(tr, ctx["scopes"]).items()}
    else:
        rate = attempted * run.samples_per_round / window_s
        metrics["samples_per_s"] = {"value": rate, "unit": "samples/s"}
        if device["platform"] == "tpu":
            metrics["mfu"] = {"value": yardstick.mfu_percent(
                cell["counts"], cell["config"], cell["traffic"], rate, chips,
                yardstick.peak(device["kind"])["flops_per_s"]), "unit": "%"}
        if device["memory_peak_bytes"] is not None:
            metrics["peak_hbm_gb"] = {
                "value": device["memory_peak_bytes"] / 1e9, "unit": "GB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    rounds = cell["config"]["check_rounds"]
    prog = (run.p0, run.params, run.records)
    del run
    gc.collect()
    jax.clear_caches()
    ref_p0, ref_recs, ref_params = reference_rounds(cell, seed, rounds)
    values = compare.readings(*prog, ref_p0, ref_params, ref_recs)
    checks = compare.check(values, cell["limits"])
    result = {"correct": compare.passed(checks) and final_ok,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced and tr.ops:
        result["breakdown"] = {"device_ops": trace_lib.top_ops(tr),
                               "idle_gaps": trace_lib.idle_gaps(tr)}
    result["setup"] = setup
    if phase_ms is not None:
        result["phases_ms"] = phase_ms
    result["readings"] = values
    result["checks"] = checks
    return result


def main(argv: list[str], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="FedGS chip benchmark: one "
                                 "run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        result = measure(cell, args.seed, args.seconds, bool(args.trace),
                         t_start)
    except NoChip as e:
        print(f"fedbench: {e}", file=sys.stderr)
        return 3
    st = result["setup"]
    print(f"setup: {st['programs_compiled']} programs compiled, "
          f"{st['programs_from_cache']} from the cache, "
          f"{st['compile_or_load_s']:.3f} s compiling or loading them",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0

"""Cells by name: ``BENCHMARK.json`` at the checkout root names each cell's
configuration and traffic mix; their files, the per-layer metric readers
and the limits of each cell's comparison sit under ``bench/`` and are found
by those names. A configuration names the modules beside it that hold its
plain reference (``reference``) and its work counts (``counts``)."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The Python file at ``path``, imported as module ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _entry(items: list, name: str, kind: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no {kind} named {name!r} in BENCHMARK.json")


def cell(name: str) -> dict:
    """Everything one cell runs with: its entry, its configuration (the
    file's contents) with its counts module, its traffic mix, its limits
    and its metrics."""
    bench = benchmark()
    work = _entry(bench["workloads"], name, "workload")
    conf = _entry(bench["configs"], work["config"], "config")
    config = _load(os.path.join(ROOT, conf["file"]))
    return {
        "workload": work,
        "config": config,
        "counts": counts(config),
        "traffic": traffic(work["traffic"]),
        "limits": limits(name),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def traffic(name: str) -> dict:
    return _load(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits(workload: str) -> dict:
    """name -> limit of every number the cell's check compares."""
    return _load(os.path.join(BENCH, "limits", f"{workload}.json"))["limits"]


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")


def reference_path(config: dict) -> str:
    return os.path.join(BENCH, "configs", f"{config['reference']}.py")


def counts_path(config: dict) -> str:
    if "counts" not in config:
        raise KeyError("the configuration names no counts module (key "
                       "'counts', a file under bench/configs/)")
    return os.path.join(BENCH, "configs", f"{config['counts']}.py")


def counts(config: dict):
    """The configuration's counts module: ``param_count(config)``,
    ``train_flops_per_sample(config, traffic)`` and ``SMOKE``, the toy
    ``model`` and ``flags`` the CPU tests run its cells with."""
    return load_module(counts_path(config), "fedbench_counts_"
                       + config["counts"])


def train_flags(config: dict, traffic_mix: dict, seed: int) -> list[str]:
    """``launch/train.py`` flags of the fused engine for one cell: the
    configuration's deployment and the traffic mix's environment."""
    flags = [f"--{k.replace('_', '-')}={v}"
             for k, v in config["deployment"].items()]
    flags += [f"--alpha={traffic_mix['alpha']}"]
    drift = traffic_mix.get("drift")
    if drift:
        flags += [f"--drift={drift['schedule']}",
                  f"--drift-period={drift['period']}",
                  f"--drift-alpha={drift['alpha']}",
                  f"--drift-churn={drift['churn_rate']}"]
    avail = traffic_mix.get("avail")
    if avail:
        flags += [f"--avail={avail['schedule']}",
                  f"--avail-up-prob={avail['up_prob']}",
                  f"--avail-dwell={avail['dwell']}",
                  f"--avail-deadline={avail['deadline']}"]
    fault = traffic_mix.get("corrupt")
    if fault:
        flags += [f"--corrupt={fault['mode']}",
                  f"--corrupt-frac={fault['frac']}",
                  f"--corrupt-prob={fault['prob']}",
                  f"--corrupt-t0={fault['t0']}",
                  f"--corrupt-scale={fault['scale']}"]
    flags += ["--engine=fused", "--eval-every=1", "--eval-chunk=1",
              f"--seed={seed}"]
    return flags + list(config.get("flags", []))

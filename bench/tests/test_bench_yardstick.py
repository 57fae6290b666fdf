"""The benchmark's yardstick on the CPU: the CNN's FLOP and parameter
counts, the peak table and the MFU formula, the trace reduction on a
synthetic trace, name resolution of every file ``BENCHMARK.json`` points
at, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from fedbench import spec, trace, yardstick  # noqa: E402

PAPER_CNN = {"model": {"image_size": 28, "channels": [32, 64], "kernel": 5,
                       "hidden": 2048, "num_classes": 62},
             "counts": "fedgs_cnn_counts"}


def test_cnn_flops_and_params():
    cnn = spec.counts(PAPER_CNN)
    # conv1 1,254,400 + conv2 20,070,400 + fc1 12,845,056 + fc2 253,952
    assert cnn.forward_flops_per_sample(PAPER_CNN) == 34_423_808
    # forward + weight gradients + input gradients of all but conv1
    assert cnn.train_flops_per_sample(PAPER_CNN, {}) == 102_017_024
    assert cnn.param_count(PAPER_CNN) == 6_603_710
    paper = {"groups": 10, "selected": 10, "batch_size": 32, "iters": 50}
    assert yardstick.samples_per_round(paper) == 160_000


def test_mfu_of_the_cnn_cells():
    """Both CNN configurations count 102,017,024 FLOP per sample, whatever
    the traffic: 24,988.38 samples/s on one v5e is 1.29403 % of its peak."""
    peak = yardstick.peak("TPU v5 lite")["flops_per_s"]
    for work in spec.benchmark()["workloads"]:
        cell = spec.cell(work["name"])
        if cell["config"]["counts"] != "fedgs_cnn_counts":
            continue
        got = yardstick.mfu_percent(cell["counts"], cell["config"],
                                    cell["traffic"], 24_988.38, 1, peak)
        assert got == pytest.approx(100 * 102_017_024 * 24_988.38 / 197e12)
        assert got == pytest.approx(1.29403, rel=1e-5)


def test_a_configuration_without_counts_is_refused():
    config = {k: v for k, v in PAPER_CNN.items() if k != "counts"}
    with pytest.raises(KeyError, match="names no counts module"):
        spec.counts(config)


def test_peak_table():
    p = yardstick.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        yardstick.peak("TPU v9 imaginary")


def _synthetic():
    # two executions of the round program ("round"), each a loop op whose
    # body ops nest inside it on the same line, a small program between
    # them, and the benchmark's host spans
    ops = [("%while.1", 1.0, 3.5), ("fusion.1", 1.0, 2.0),
           ("_conv_pool_kernel", 2.0, 3.0), ("_residual_kernel", 3.0, 3.5),
           ("copy", 4.0, 4.25),
           ("%while.1", 5.0, 7.5), ("fusion.1", 5.0, 6.0),
           ("_conv_pool_kernel", 6.0, 7.5)]
    modules = [("round", 1.0, 3.5), ("tiny", 4.0, 4.25), ("round", 5.0, 7.5)]
    spans = [("bench.dispatch", 0.9, 1.0), ("bench.fetch", 1.0, 4.5),
             ("bench.dispatch", 4.5, 5.0), ("bench.fetch", 5.0, 8.0)]
    return trace.Trace(ops=[ops], modules=[modules], spans=spans)


def test_trace_reduction_on_a_synthetic_trace():
    tr = _synthetic()
    assert tr.window == (0.9, 8.0)
    # busy: [1, 3.5] + [4, 4.25] + [5, 7.5] = 2.5 + 0.25 + 2.5
    assert trace.busy_s(tr) == pytest.approx(5.25)
    assert trace.idle_share(tr) == pytest.approx(1 - 5.25 / 7.1)
    assert trace.op_time_s(tr, ("_conv_pool_kernel",)) == pytest.approx(2.5)
    assert trace.op_time_s(tr, ("_residual", "copy")) == pytest.approx(0.75)
    # the loop ops' own time is what their body ops leave uncovered: 0
    assert trace.top_ops(tr, 3) == [["_conv_pool_kernel", pytest.approx(2.5)],
                                    ["fusion.1", pytest.approx(2.0)],
                                    ["_residual_kernel", pytest.approx(0.5)]]
    # round boundary: 3.5 -> 5.0 is 1.5 s, of which the copy fills 0.25
    [(gap, span)] = trace.round_boundary_gaps(tr)
    assert gap == pytest.approx(1.25) and span == "bench.fetch"
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["bench.dispatch", pytest.approx(0.75)]  # 4.25 -> 5.0
    assert sorted(round(g[1], 6) for g in gaps) == [0.1, 0.5, 0.5, 0.75]


def test_self_times_of_nested_events():
    evs = [("loop", 0.0, 10.0), ("a", 0.0, 4.0), ("b", 1.0, 2.0),
           ("c", 5.0, 10.0), ("d", 12.0, 13.0)]
    assert sorted(trace.self_times(evs)) == [
        ("a", pytest.approx(3.0)), ("b", pytest.approx(1.0)),
        ("c", pytest.approx(5.0)), ("d", pytest.approx(1.0)),
        ("loop", pytest.approx(1.0))]


def test_union_of_nested_and_disjoint_intervals():
    evs = [("a", 0.0, 10.0), ("b", 2.0, 3.0), ("c", 9.0, 12.0),
           ("d", 20.0, 21.0)]
    assert trace.union_s(evs) == pytest.approx(13.0)


def test_every_name_in_the_benchmark_resolves():
    bench = spec.benchmark()
    for conf in bench["configs"]:
        path = os.path.join(ROOT, conf["file"])
        assert os.path.isfile(path), path
        assert conf["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for m in bench["per_layer"]:
        reader = spec.load_module(spec.metric_path(m["name"]), "m")
        assert callable(reader.read), m["name"]
    for work in bench["workloads"]:
        cell = spec.cell(work["name"])
        assert os.path.isfile(spec.reference_path(cell["config"]))
        assert cell["limits"], work["name"]
        counts = cell["counts"]
        assert counts.__file__ == spec.counts_path(cell["config"])
        assert callable(counts.train_flops_per_sample)
        assert {"model", "flags"} <= set(counts.SMOKE)
        if cell["config"]["counts"] == "fedgs_cnn_counts":
            assert counts.param_count(cell["config"]) == 6_603_710
        flags = spec.train_flags(cell["config"], cell["traffic"], 7)
        assert "--seed=7" in flags and "--engine=fused" in flags
        names = {m["name"] for m in cell["end_to_end"]}
        assert {"samples_per_s", "mfu", "peak_hbm_gb", "setup_s"} <= names
        assert cell["per_layer"], work["name"]


def _run(cwd, timeout=300):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper.static",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=timeout)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    res = _run(ROOT)
    assert res.returncode != 0
    assert not _has_result(res.stdout)
    assert "TPU" in res.stderr


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert not _has_result(res.stdout)

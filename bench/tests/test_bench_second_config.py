"""A second configuration comes as new files only: in a copy of the
benchmark, a configuration with a model that is no CNN, its own counts
module, a traffic mix, a limits file and a reader of a scope the program
does not have yet, plus entries appended to ``BENCHMARK.json``. The copy's
unchanged harness resolves the cell, counts its work, computes its MFU and
reads the new scope, and every file that was there before is as it was."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NEW_FILES = {
    "bench/configs/toy_moe.json": json.dumps({
        "source": "a made-up decoder with routed experts",
        "model": {"vocab_size": 1024, "hidden_size": 64, "num_layers": 2,
                  "n_routed_experts": 8, "moe_intermediate_size": 32},
        "deployment": {"groups": 4, "devices_per_group": 8, "selected": 2,
                       "batch_size": 3, "iters": 5},
        "check_rounds": 2,
        "reference": "toy_moe_reference",
        "counts": "toy_moe_counts"}),
    "bench/configs/toy_moe_counts.py": textwrap.dedent('''\
        SMOKE = {"model": {"vocab_size": 64, "hidden_size": 8,
                           "num_layers": 1, "n_routed_experts": 2,
                           "moe_intermediate_size": 4},
                 "flags": ["--model=toy_moe"]}


        def param_count(config):
            m = config["model"]
            d, f, e = (m["hidden_size"], m["moe_intermediate_size"],
                       m["n_routed_experts"])
            return 2 * m["vocab_size"] * d + m["num_layers"] * e * 3 * d * f


        def train_flops_per_sample(config, traffic):
            # six FLOP a parameter a token, over the traffic's sequences
            return 6 * param_count(config) * traffic["seq_len"]
        '''),
    "bench/traffic/short_seqs.json": json.dumps(
        {"why": "short sequences", "alpha": 0.3, "seq_len": 16}),
    "bench/limits/toy_moe.short.json": json.dumps(
        {"limits": {"update1": 0.01, "loss": 0.001}}),
    "bench/metrics/moe.dispatch.ms.py": textwrap.dedent('''\
        from fedbench import phases


        def read(ctx):
            t = phases.scope_time_s(ctx["trace"], ctx["op_names"],
                                    "moe.dispatch")
            return 1e3 * t / ctx["rounds"] if t > 0 else None
        '''),
}
ENTRIES = {
    "configs": {"name": "toy_moe", "source": "https://example.org/toy-moe",
                "file": "bench/configs/toy_moe.json", "reduced": [],
                "why": "a model that is no CNN"},
    "workloads": {"name": "toy_moe.short", "config": "toy_moe",
                  "traffic": "short_seqs", "chips": 1,
                  "why": "short sequences through the experts"},
    "per_layer": {"name": "moe.dispatch.ms", "unit": "ms",
                  "better": "lower", "source": "device_trace",
                  "layer": "experts", "moves": "samples_per_s",
                  "workloads": ["toy_moe.short"]},
}

# run in the copy, with the copy's harness on the path
CHECK = textwrap.dedent('''\
    import json, sys
    sys.path.insert(0, "bench")
    from fedbench import harness, phases, spec, trace, yardstick

    cell = spec.cell("toy_moe.short")
    counts = cell["counts"]
    assert counts.__file__ == spec.counts_path(cell["config"])
    n = counts.param_count(cell["config"])
    assert n == 2 * 1024 * 64 + 2 * 8 * 3 * 64 * 32, n
    assert cell["limits"] == {"update1": 0.01, "loss": 0.001}
    assert yardstick.samples_per_round(cell["config"]["deployment"]) == 120
    mfu = yardstick.mfu_percent(counts, cell["config"], cell["traffic"],
                                1000.0, 1, 197e12)
    assert abs(mfu - 100 * 6 * n * 16 * 1000.0 / 197e12) < 1e-12, mfu

    body = "jit(run_chunk)/while/body/fedgs.train/"
    text = "\\n".join([
        "ENTRY %main.3 (p: f32[8]) -> f32[8] {",
        f'  %fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %p), '
        f'metadata={{op_name="{body}moe.dispatch/dot_general"}}',
        f'  %fusion.2 = f32[8]{{0}} fusion(f32[8]{{0}} %p), '
        f'metadata={{op_name="{body}dot_general"}}', "}"])
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1.0, 1.5),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 1.5, 3.0)]
    tr = trace.Trace(ops=[ops], modules=[[("round", 1.0, 3.0)]],
                     spans=[("bench.dispatch", 0.9, 1.0),
                            ("bench.fetch", 1.0, 3.0)])
    assert phases.scope_time_s(tr, phases.op_names(text),
                               "moe.dispatch") == 0.5
    ctx = harness.reader_context(cell, tr, text, "TPU v5 lite")
    assert ctx["counts"] is counts and ctx["traffic"]["seq_len"] == 16
    got = harness.read_per_layer(cell, ctx)
    assert got["moe.dispatch.ms"] == {"value": 250.0, "unit": "ms"}, got
    assert got["train.ms"] == {"value": 1000.0, "unit": "ms"}, got
    print(json.dumps(sorted(got)))
    ''')


def _digests(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_second_configuration_is_new_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    old = json.loads((tmp_path / "BENCHMARK.json").read_text())

    for rel, text in NEW_FILES.items():
        assert not (tmp_path / rel).exists(), rel
        (tmp_path / rel).write_text(text)
    bench = json.loads(json.dumps(old))
    for key, entry in ENTRIES.items():
        bench[key].append(entry)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    res = subprocess.run([sys.executable, "-c", CHECK], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    read = json.loads(res.stdout.splitlines()[-1])
    assert "moe.dispatch.ms" in read and "train.ms" in read

    after = _digests(tmp_path)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(before) == set(NEW_FILES)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(old[key])] == old[key]

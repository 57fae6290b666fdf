"""Device time by phase of the round: the attribution of device ops to the
program's phase scopes on a synthetic trace and module text, and the
scopes in each cell's compiled round."""
import pytest

from tiny_cell import SEED, WORKLOADS, tiny
from fedbench import harness, phases, spec, trace

BODY = "jit(run_chunk)/while/body/"
# a compiled module's instructions with their op_name metadata (None: the
# compiler's own, such as a layout copy): a loop op, the sampler's gather,
# a schedule op whose forward runs under the train scope (the innermost
# scope counts), the backward, a layout copy, the update, the eval cond
INSTRUCTIONS = [
    ("while.1", "while", "jit(run_chunk)/while"),
    ("fusion.1", "fusion", BODY + "fedgs.sample/gather"),
    ("fusion.2", "fusion", BODY + "fedgs.train/jvp(fedgs.schedule)/mul"),
    ("fusion.3", "fusion", BODY + "transpose(jvp(fedgs.train))/dot_general"),
    ("copy.4", "copy", None),
    ("fusion.5", "fusion", BODY + "fedgs.eq4/sub"),
    ("conditional.6", "conditional", "jit(run_chunk)/engine.eval/cond"),
    ("fusion.7", "fusion", "jit(run_chunk)/engine.eval/dot_general"),
]
SPANS = {"while.1": (1.0, 5.0), "fusion.1": (1.0, 2.0),
         "fusion.2": (2.0, 2.5), "fusion.3": (2.5, 4.0),
         "copy.4": (4.0, 4.5), "fusion.5": (4.5, 4.75),
         "conditional.6": (5.5, 6.0), "fusion.7": (5.5, 5.9)}


def _module_text():
    lines = ["ENTRY %main.9 (p: f32[8]) -> f32[8] {"]
    for name, kind, op_name in INSTRUCTIONS:
        meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
            if op_name else ""
        lines.append(f"  %{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p){meta}")
    return "\n".join(lines + ["}"])


def _synthetic_trace():
    # a TPU op event is named by its instruction's text without metadata;
    # the loop op's event spans its body's ops on the same line
    ops = [(f"%{n} = f32[8]{{0:T(128)}} {k}(f32[8]{{0:T(128)}} %p)",
            *SPANS[n]) for n, k, _ in INSTRUCTIONS]
    spans = [("bench.dispatch", 0.9, 1.0), ("bench.fetch", 1.0, 6.5)]
    return trace.Trace(ops=[ops], modules=[[("round", 1.0, 6.0)]],
                       spans=spans)


def test_scopes_from_compiled_module_text():
    """Each instruction takes the innermost phase of its op_name; the
    compiler's own instructions and the loop op have none."""
    assert phases.op_scopes(_module_text()) == {
        "fusion.1": "fedgs.sample", "fusion.2": "fedgs.schedule",
        "fusion.3": "fedgs.train", "fusion.5": "fedgs.eq4",
        "conditional.6": "engine.eval", "fusion.7": "engine.eval"}
    assert phases.innermost_phase(
        "jit(f)/fedgs.select/jit(gbp_cs_minimize)/gbp_cs/pallas_call") == \
        "fedgs.select"
    assert phases.innermost_phase("jit(f)/closed_call/add") is None


def test_phase_attribution_on_a_synthetic_trace():
    tr = _synthetic_trace()
    got = phases.phase_times(tr, phases.op_scopes(_module_text()))
    assert got == pytest.approx({
        "fedgs.sample": 1.0, "fedgs.schedule": 0.5, "fedgs.select": 0.0,
        "fedgs.train": 1.5, "fedgs.eq4": 0.25, "fedgs.eq5": 0.0,
        # the cond's own 0.1 s and its body's 0.4 s
        "engine.eval": 0.5,
        # the loop op's own 0.25 s (4 s less its body) and the copy's 0.5 s
        "unscoped": 0.75})
    # self times: the phases and the rest add up to the busy time
    assert sum(got.values()) == pytest.approx(trace.busy_s(tr))


def test_without_scopes_everything_is_unscoped():
    """A program without the scopes (or an empty map) leaves all of the
    busy time unscoped, and the existing reductions read the same."""
    tr = _synthetic_trace()
    got = phases.phase_times(tr, {})
    assert got[phases.UNSCOPED] == pytest.approx(trace.busy_s(tr))
    assert sum(got.values()) == got[phases.UNSCOPED]
    assert trace.busy_s(tr) == pytest.approx(5.25 - 0.25 - 0.5)


def test_scope_time_holds_every_op_under_the_scope():
    """Unlike a phase, a scope counts every op whose path holds it, the
    innermost or not: ``fedgs.train`` holds the schedule op run under it."""
    tr, names = _synthetic_trace(), phases.op_names(_module_text())
    assert "copy.4" not in names
    assert names["fusion.3"] == \
        BODY + "transpose(jvp(fedgs.train))/dot_general"
    assert phases.scope_time_s(tr, names, "fedgs.train") == \
        pytest.approx(2.0)
    assert phases.scope_time_s(tr, names, "fedgs.schedule") == \
        pytest.approx(0.5)
    assert phases.scope_time_s(tr, names, "engine.eval") == \
        pytest.approx(0.5)
    assert phases.scope_time_s(tr, names, "moe.dispatch") == 0.0


# per traced round (2 in the context) of the synthetic trace, in ms
READ_MS = {"train.ms": 750.0, "sample.ms": 500.0, "eq4.ms": 125.0,
           "unscoped.ms": 375.0, "select.ms": None}


@pytest.mark.parametrize("metric", sorted(READ_MS))
def test_phase_readers_on_a_synthetic_trace(metric):
    """Each per-phase reader reads its phase's self time per round from
    the context the harness gives it; a phase the compiled round does
    not name (here ``fedgs.select``) reads nothing, and so does every
    reader of a round compiled without scopes."""
    tr, text = _synthetic_trace(), _module_text()
    cell = spec.cell(WORKLOADS[0])
    reader = spec.load_module(spec.metric_path(metric), "m")
    ctx = harness.reader_context(cell, tr, text, "TPU v5 lite")
    assert ctx["rounds"] == 2
    got = reader.read(ctx)
    assert got == (None if READ_MS[metric] is None
                   else pytest.approx(READ_MS[metric]))
    bare = harness.reader_context(cell, tr, "ENTRY %m {}", "TPU v5 lite")
    assert reader.read(bare) is None
    # the phases and unscoped add up to the busy time per round
    total = sum(phases.phase_ms_per_round(ctx, p) or 0.0
                for p in phases.PHASES + (phases.UNSCOPED,))
    assert total == pytest.approx(1e3 * trace.busy_s(tr) / 2)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_each_cell_compiles_its_phases(workload):
    """Every phase the cell runs reaches the compiled round's op_names;
    ``fedgs.schedule`` only where the traffic has a schedule."""
    import jax.numpy as jnp
    from repro.core import engine
    from repro.launch import train
    cell = tiny(workload)
    args = train.parse_args(spec.train_flags(cell["config"], cell["traffic"],
                                             SEED))
    exp = train.build_fedgs_experiment(args, train.build_world(args), None)
    hlo = engine._make_chunk_fn(exp, 1, 1).lower(
        exp.init_state, jnp.arange(1, dtype=jnp.int32)).compile().as_text()
    scheduled = "avail" in cell["traffic"] or "corrupt" in cell["traffic"]
    want = set(phases.PHASES) - ({"fedgs.schedule"} if not scheduled
                                 else set())
    assert set(phases.op_scopes(hlo).values()) == want

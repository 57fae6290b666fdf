"""The check that decides ``correct``, on the CPU at a size a test run can
hold: each cell's sound run passes its limits, the control (the plain
reference computed in bfloat16, put in the program's place) fails them,
and the reference compiles one round program for every seed."""
import pytest

from tiny_cell import SEED, WORKLOADS, measure, tiny
from fedbench import compare, harness, spec


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    res = measure(tiny(workload))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bfloat16_control_fails_the_limits(workload):
    cell = tiny(workload)
    rounds = cell["config"]["check_rounds"]
    ref = harness.reference_rounds(cell, SEED, rounds)
    ctl = harness.reference_rounds(cell, SEED, rounds, control=True)
    values = compare.readings(ctl[0], ctl[2], ctl[1], ref[0], ref[2], ref[1])
    assert not compare.passed(compare.check(values, cell["limits"])), values


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_lowers_to_one_program_for_every_seed(workload):
    """Everything the reference draws from the seed reaches its compiled
    round as an argument, so a fresh seed finds the round in the cache."""
    import jax
    import jax.numpy as jnp
    cell = tiny(workload)
    mod = spec.load_module(spec.reference_path(cell["config"]),
                           "fedbench_reference")
    texts = set()
    for seed in (SEED, 7):
        ref = mod.Reference(cell["config"], cell["traffic"], seed)
        texts.add(jax.jit(ref._round).lower(
            ref.data, ref.initial_carry(), jnp.int32(0)).as_text())
    assert len(texts) == 1

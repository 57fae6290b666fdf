"""A whole run of each cell on the CPU at a size a test run can hold, with
the look for a chip skipped and the timed path broken underneath: a round
that returns its state unchanged, and half of every batch left out with the
mean taken over the rest. Each must come out not correct."""
import dataclasses

import pytest

from tiny_cell import WORKLOADS, measure, tiny


@pytest.fixture
def unchanged_state(monkeypatch):
    """Each round returns the state it was given (metrics still computed)."""
    from repro.launch import train
    build = train.build_fedgs_experiment

    def broken(*a, **kw):
        exp = build(*a, **kw)
        return dataclasses.replace(
            exp, round_fn=lambda s, r: (s, exp.round_fn(s, r)[1]))

    monkeypatch.setattr(train, "build_fedgs_experiment", broken)


@pytest.fixture
def half_batch(monkeypatch):
    """Every member's loss is the mean over the first half of its batch:
    the sampler hands each member its batch's first half twice (arrays of
    shape (groups, members, batch, ...)), whatever the model."""
    import jax
    import jax.numpy as jnp
    from repro.launch import train
    make = train.fedgs_sampler

    def halved(*a, **kw):
        sampler = make(*a, **kw)

        def selected_batch(*b):
            def twice(x):
                h = x[:, :, : x.shape[2] // 2]
                return jnp.concatenate([h, h], axis=2)
            return jax.tree.map(twice, sampler.selected_batch(*b))
        return sampler._replace(selected_batch=selected_batch)

    monkeypatch.setattr(train, "fedgs_sampler", halved)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_state_left_unchanged_is_not_correct(workload, unchanged_state):
    res = measure(tiny(workload))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_half_batch_is_not_correct(workload, half_batch):
    res = measure(tiny(workload))
    assert not res["correct"], res["checks"]

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
    """Every member's loss is the mean over the first half of its batch."""
    from repro.launch import train
    from repro.models import cnn
    member, grouped = cnn.loss_fn, train.fedgs_group_loss_fn

    def member_half(p, batch):
        x, y = batch
        return member(p, (x[: x.shape[0] // 2], y[: y.shape[0] // 2]))

    def grouped_half(args):
        fn = grouped(args)
        if fn is None:
            return None
        n = args.batch_size // 2
        return lambda gp, b: fn(gp, (b[0][:, :, :n], b[1][:, :, :n]))

    monkeypatch.setattr(cnn, "loss_fn", member_half)
    monkeypatch.setattr(train, "fedgs_group_loss_fn", grouped_half)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_state_left_unchanged_is_not_correct(workload, unchanged_state):
    res = measure(tiny(workload))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_half_batch_is_not_correct(workload, half_batch):
    res = measure(tiny(workload))
    assert not res["correct"], res["checks"]

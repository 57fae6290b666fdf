"""The round's profiler names and work counters: GBP-CS permutation steps
carried into the round's metrics, and the engine's host spans."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import femnist_cnn
from repro.core import engine, fedgs, selection
from repro.data import (DeviceStream, PartitionConfig, make_device_sampler,
                        make_partition)
from repro.models import cnn

M, K, L, T = 3, 8, 4, 2


@pytest.fixture(scope="module")
def fleet():
    part = make_partition(PartitionConfig(num_factories=M,
                                          devices_per_factory=K, seed=1))
    sampler = make_device_sampler(
        DeviceStream.from_partition(part, batch_size=4, seed=1))
    params = cnn.init_cnn(jax.random.PRNGKey(0), femnist_cnn.smoke_config())
    return part, sampler, params


def _rounds(fleet, t0s, **cfg_kw):
    """The round body's per-iteration metrics from the seed's key and an
    empty selection state, for each start iteration in ``t0s``."""
    part, sampler, params = fleet
    cfg = fedgs.FedGSConfig(num_groups=M, devices_per_group=K,
                            num_selected=L, num_presampled=1,
                            iters_per_round=T, rounds=2, lr=0.05,
                            batch_size=4, gbp_max_iters=16, **cfg_kw)
    body = jax.jit(fedgs.make_round_body(cnn.loss_fn, cfg, sampler))
    key = jax.random.PRNGKey(cfg.seed)
    p_real = jnp.asarray(part.p_real, jnp.float32)
    mets = [body(fedgs.replicate_for_groups(params, M), key,
                 fedgs.init_selection_state(cfg), jnp.int32(t0), p_real)[3]
            for t0 in t0s]
    return cfg, key, p_real, mets


def test_gbp_cs_steps_are_the_solver_iterations(fleet):
    """Each iteration's counters equal the per-group solver's iterations:
    their sum, and groups x the largest, which bounds the sum."""
    _, sampler, _ = fleet
    cfg, key, p_real, [mets] = _rounds(fleet, [0])
    gids = jnp.arange(M, dtype=jnp.int32)
    for t in range(T):
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, M)
        counts = sampler.counts(jnp.int32(t), gids)
        iters = [int(selection.select_clients_via_gbp_cs(
            keys[g], counts[g], p_real, L, cfg.num_presampled,
            max_iters=cfg.gbp_max_iters).iterations) for g in range(M)]
        assert float(mets["gbp_cs_steps"][t]) == sum(iters)
        assert float(mets["gbp_cs_step_slots"][t]) == M * max(iters)
    assert np.all(np.asarray(mets["gbp_cs_steps"])
                  <= np.asarray(mets["gbp_cs_step_slots"]))
    assert float(np.sum(mets["gbp_cs_steps"])) > 0


def test_kept_committee_counts_no_steps(fleet):
    """Static committees (``reselect_every=0``) solve at t=0 only: a later
    round keeps the carried masks, and random selection never solves."""
    *_, (first, later) = _rounds(fleet, [0, T], reselect_every=0)
    assert float(first["gbp_cs_steps"][0]) > 0
    assert np.all(np.asarray(first["gbp_cs_steps"][1:]) == 0)
    assert np.all(np.asarray(later["gbp_cs_steps"]) == 0)
    assert np.all(np.asarray(later["gbp_cs_step_slots"]) == 0)
    *_, [rand] = _rounds(fleet, [0], selection="random")
    assert np.all(np.asarray(rand["gbp_cs_step_slots"]) == 0)


def test_counters_reach_the_round_record(fleet):
    part, sampler, params = fleet
    cfg = fedgs.FedGSConfig(num_groups=M, devices_per_group=K,
                            num_selected=L, num_presampled=1,
                            iters_per_round=T, rounds=1, lr=0.05,
                            batch_size=4, gbp_max_iters=16)
    _, [rec] = fedgs.run_fedgs_fused(params, cnn.loss_fn, sampler,
                                     part.p_real, cfg)
    assert 0 < rec.gbp_cs_steps <= rec.gbp_cs_step_slots
    assert rec.to_dict()["gbp_cs_steps"] == rec.gbp_cs_steps


def test_records_from_metrics_spans_in_a_profile(tmp_path):
    """A profile of the host's fetch holds the program's own spans."""
    from jax.profiler import ProfileData
    mets = {"loss": jnp.asarray([1.0]), "gbp_cs_steps": jnp.asarray([3.0])}
    jax.profiler.start_trace(str(tmp_path))
    [rec] = engine.records_from_metrics(0, mets)
    jax.profiler.stop_trace()
    assert rec.gbp_cs_steps == 3.0
    [path] = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                       recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host") for line in plane.lines
             for ev in line.events]
    assert names.count("engine.wait") == 1
    assert names.count("engine.fetch") == 1

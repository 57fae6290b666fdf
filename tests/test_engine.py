"""Unified experiment engine (core.engine, DESIGN.md §12): chunked
multi-round scan == per-round loop == two-phase host loop, fused baselines
== host run_baseline to 1e-5, on-device eval, typed RoundRecord."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import femnist_cnn
from repro.core import baselines, engine, fedgs
from repro.data import (DeviceBackedStreams, DeviceStream, HostClientPool,
                        PartitionConfig, femnist, make_client_pool,
                        make_device_sampler, make_partition)
from repro.models import cnn

CFG = dict(num_groups=4, devices_per_group=8, num_selected=4,
           num_presampled=1, iters_per_round=5, rounds=4, lr=0.05,
           batch_size=8, gbp_max_iters=16)


_PROBE = baselines.linear_probe_model()


def linear_loss(params, batch):
    x, y = batch
    return baselines.softmax_xent(_PROBE.apply(params, x), y)


@pytest.fixture(scope="module")
def setup():
    part = make_partition(PartitionConfig(num_factories=4,
                                          devices_per_factory=8, seed=0))
    stream = DeviceStream.from_partition(part, batch_size=8, seed=0)
    sampler = make_device_sampler(stream)
    params = _PROBE.init(jax.random.PRNGKey(0))
    return part, stream, sampler, params


def _max_diff(a, b):
    return max(jax.tree.leaves(
        jax.tree.map(lambda x, y: float(jnp.abs(x - y).max()), a, b)))


def test_chunked_scan_equals_per_round_and_host(setup):
    """Satellite acceptance: chunked multi-round scan == per-round loop ==
    two-phase host loop for FEDGS (host/fused engines)."""
    part, _, sampler, params = setup
    cfg = fedgs.FedGSConfig(**CFG)
    per_round, logs1 = fedgs.run_fedgs_fused(
        params, linear_loss, sampler, part.p_real, cfg)           # chunk=1
    chunked, logs2 = fedgs.run_fedgs_fused(
        params, linear_loss, sampler, part.p_real, cfg, chunk=2)
    one_shot, logs3 = fedgs.run_fedgs_fused(
        params, linear_loss, sampler, part.p_real, cfg, chunk=cfg.rounds)
    host, _ = fedgs.run_fedgs(
        params, linear_loss, DeviceBackedStreams(sampler), part.p_real, cfg)
    assert _max_diff(per_round, chunked) < 1e-5
    assert _max_diff(per_round, one_shot) < 1e-5
    assert _max_diff(chunked, host) < 1e-5
    np.testing.assert_allclose([l.loss for l in logs1],
                               [l.loss for l in logs2], atol=1e-5)
    np.testing.assert_allclose([l.divergence for l in logs1],
                               [l.divergence for l in logs3], atol=1e-5)


def test_chunked_scan_equals_per_round_sharded(setup):
    """The sharded leg: chunked scan inside shard_map (1-device 'groups'
    mesh — the transparent fallback) == unsharded chunked == per-round."""
    part, _, sampler, params = setup
    cfg = fedgs.FedGSConfig(**CFG)
    mesh = jax.make_mesh((1,), ("groups",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    ref, _ = fedgs.run_fedgs_fused(params, linear_loss, sampler,
                                   part.p_real, cfg)
    sharded, _ = fedgs.run_fedgs_fused(params, linear_loss, sampler,
                                       part.p_real, cfg, mesh=mesh, chunk=2)
    assert _max_diff(ref, sharded) < 1e-5


def test_dispatch_count_is_ceil_rounds_over_chunk(setup):
    part, _, sampler, params = setup
    cfg = fedgs.FedGSConfig(**{**CFG, "rounds": 5})
    exp = fedgs.make_fedgs_experiment(params, linear_loss, sampler,
                                      part.p_real, cfg, unroll=1)
    chunks = []
    _, logs = engine.run_experiment(exp, cfg.rounds, chunk=2,
                                    on_chunk=lambda r0, n: chunks.append(n))
    assert chunks == [2, 2, 1]                     # partial last chunk
    assert len(chunks) == engine.num_dispatches(cfg.rounds, 2) == 3
    assert [l.round for l in logs] == list(range(cfg.rounds))


@pytest.mark.parametrize("name", ["fedavg", "fedadam"])
def test_fused_baseline_matches_host_run_baseline(name, setup):
    """Satellite acceptance: fused-baseline vs host run_baseline parameter
    parity to 1e-5 (same PRNG discipline) for FedAvg and FedAdam."""
    part, _, _, _ = setup
    stream = DeviceStream.from_partition(part, batch_size=8, seed=3)
    model = cnn.make_model_api(femnist_cnn.smoke_config())
    pool = make_client_pool(stream, clients=4, steps=2)
    cfg = baselines.BaselineConfig(clients_per_round=4, local_steps=2,
                                   lr=0.05, rounds=4, seed=0)
    strat = baselines.all_strategies(model)[name]
    (pf, ef), flogs = baselines.run_baseline(model, strat, pool, cfg,
                                             chunk=2)
    (ph, eh), hlogs = baselines.run_baseline(model, strat,
                                             HostClientPool(pool), cfg)
    assert _max_diff(pf, ph) < 1e-5
    np.testing.assert_allclose([l.loss for l in flogs],
                               [l.loss for l in hlogs], atol=1e-5)
    assert flogs[0].strategy == hlogs[0].strategy == strat.name


def test_client_pool_is_pure_in_round(setup):
    part, _, _, _ = setup
    stream = DeviceStream.from_partition(part, batch_size=8, seed=3)
    pool = make_client_pool(stream, clients=4, steps=2)
    (i1, l1), w1 = pool.round_batches(jnp.int32(7))
    (i2, l2), w2 = pool.round_batches(jnp.int32(7))
    (i3, _), _ = pool.round_batches(jnp.int32(8))
    assert i1.shape == (4, 2, 8, 28, 28) and l1.shape == (4, 2, 8)
    assert bool(jnp.all(i1 == i2)) and bool(jnp.all(l1 == l2))
    assert not bool(jnp.all(i1 == i3))            # the stream advances
    assert bool(jnp.all(w1 == 2 * 8))


def test_on_device_eval_matches_direct_eval(setup):
    """Engine eval (lax.cond inside the round scan, device-resident test
    set) reports the same numbers as calling eval_fn on the returned
    params; non-eval rounds log None."""
    part, _, sampler, params = setup
    tx, ty = femnist.make_test_set(n_per_class=2)
    eval_fn = cnn.make_eval_fn(tx, ty, apply_fn=_PROBE.apply)
    cfg = fedgs.FedGSConfig(**CFG)
    final, logs = fedgs.run_fedgs_fused(
        params, linear_loss, sampler, part.p_real, cfg, chunk=2,
        eval_fn=eval_fn, eval_every=2)
    assert [l.test_accuracy is not None for l in logs] == \
        [False, True, False, True]
    tl, ta = eval_fn(final)
    assert abs(float(tl) - logs[-1].test_loss) < 1e-5
    assert abs(float(ta) - logs[-1].test_accuracy) < 1e-6


def test_eval_fn_batched_matches_unbatched():
    tx, ty = femnist.make_test_set(n_per_class=2)   # 124 samples
    params = cnn.init_cnn(jax.random.PRNGKey(1), femnist_cnn.smoke_config())
    full = cnn.make_eval_fn(tx, ty)
    batched = cnn.make_eval_fn(tx, ty, batch=62)
    l1, a1 = full(params)
    l2, a2 = batched(params)
    assert abs(float(l1) - float(l2)) < 1e-5
    assert abs(float(a1) - float(a2)) < 1e-6
    # mean NLL/accuracy semantics match the host evaluate()
    l3, a3 = cnn.evaluate(params, jnp.asarray(tx), jnp.asarray(ty))
    assert abs(float(l1) - l3) < 1e-4 and abs(float(a1) - a3) < 1e-6
    with pytest.raises(ValueError, match="divide"):
        cnn.make_eval_fn(tx, ty, batch=100)


def test_run_experiment_preserves_init_state_and_reruns(setup):
    """Donation must not eat caller-owned arrays: the same Experiment runs
    twice with identical results and the caller's params stay alive; a
    host-style (non-jittable) eval_fn fails with an actionable TypeError."""
    part, _, sampler, params = setup
    cfg = fedgs.FedGSConfig(**{**CFG, "rounds": 2})
    exp = fedgs.make_fedgs_experiment(params, linear_loss, sampler,
                                      part.p_real, cfg, unroll=1)
    s1, _ = engine.run_experiment(exp, cfg.rounds, chunk=2)
    s2, _ = engine.run_experiment(exp, cfg.rounds, chunk=2)
    assert _max_diff(exp.params_fn(s1), exp.params_fn(s2)) == 0.0
    assert bool(jnp.all(jnp.isfinite(params["w"])))   # not donated away

    def host_eval(p):                                  # float() on a tracer
        return float(jnp.sum(p["w"])), 0.0
    exp2 = fedgs.make_fedgs_experiment(params, linear_loss, sampler,
                                       part.p_real, cfg, eval_fn=host_eval,
                                       unroll=1)
    with pytest.raises(TypeError, match="jittable"):
        engine.run_experiment(exp2, cfg.rounds, eval_every=1, chunk=2)


def test_round_record_typed_log():
    rec = engine.RoundRecord(round=3, loss=1.5, strategy="fedavg")
    assert rec.test_accuracy is None and math.isnan(rec.divergence)
    assert math.isnan(rec.group_discrepancy) and math.isnan(rec.reselections)
    d = rec.to_dict()
    assert d["round"] == 3 and d["strategy"] == "fedavg"
    assert set(d) == {"round", "loss", "divergence", "test_loss",
                      "test_accuracy", "strategy", "group_discrepancy",
                      "selection_distance", "reselections", "participation",
                      "staleness_mean", "staleness_max", "dark_selected",
                      "corrupted_selected", "clipped_fraction", "rollbacks",
                      "agg_residual", "bytes_int", "bytes_ext",
                      "compress_error", "gbp_cs_steps", "gbp_cs_step_slots"}
    # NaN telemetry slots (strategies without them) -> None, JSON-safe
    assert d["group_discrepancy"] is None and d["reselections"] is None
    assert d["participation"] is None and d["staleness_max"] is None
    # records_from_metrics: NaN eval slots -> None, telemetry forwarded
    recs = engine.records_from_metrics(
        10, {"loss": jnp.asarray([1.0, 2.0]),
             "test_accuracy": jnp.asarray([float("nan"), 0.5]),
             "reselections": jnp.asarray([5.0, 0.0])}, strategy="s")
    assert recs[0].round == 10 and recs[0].test_accuracy is None
    assert recs[1].test_accuracy == 0.5 and recs[1].strategy == "s"
    assert recs[0].reselections == 5.0 and recs[1].reselections == 0.0

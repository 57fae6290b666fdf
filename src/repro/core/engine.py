"""Unified device-resident experiment engine (DESIGN.md §12).

Every Table II experiment — FEDGS and all fifteen comparison strategies —
is "a state pytree plus a pure one-round function". This module abstracts
that behind the :class:`Experiment` protocol and drives it with ONE
execution engine:

* **Chunked multi-round scan** — instead of one jitted dispatch per
  federated round, the engine ``lax.scan``s over *chunks of rounds*
  (``chunk`` rounds per host dispatch), so an R-round experiment costs
  ⌈R/chunk⌉ host round-trips. Per-round metrics come back stacked
  ``(chunk, ...)`` once per dispatch.
* **On-device eval** — the test set lives on the accelerator and periodic
  evaluation runs *inside* the scan body behind a ``lax.cond`` (a no-op
  branch on non-eval rounds), so evaluating every ``eval_every`` rounds
  costs no extra dispatches and no host↔device test-set transfers.
* **Typed logs** — one :class:`RoundRecord` per round, shared by the
  engine, the host loops, ``benchmarks/`` and ``launch/train.py`` (no more
  mutable RoundLog here, list-of-dicts there).

``core.fedgs.make_fedgs_experiment`` and ``core.baselines
.make_baseline_experiment`` are the two producers; both feed
:func:`run_experiment`, so the FEDGS-vs-baselines comparison benchmarks the
*strategies*, never two different harnesses.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any
Array = jax.Array

_NAN = float("nan")


class RoundRecord(NamedTuple):
    """One federated round's log entry — THE log record of the repo.

    ``test_loss``/``test_accuracy`` are None on rounds without eval.
    Field names match the old ``fedgs.RoundLog`` so attribute access is
    unchanged; ``to_dict`` replaces ``vars(log)`` / the baselines' ad-hoc
    dicts for JSON output. The heterogeneity-telemetry fields (DESIGN.md
    §13) are NaN for strategies that don't report them: ``group_discrepancy``
    is the mean per-group data-distribution discrepancy vs the global
    distribution, ``selection_distance`` the GBP-CS objective ``d`` of the
    last rebuild, ``reselections`` the number of GBP-CS rebuilds this round.
    The availability-telemetry fields (DESIGN.md §14.4) are NaN without an
    availability schedule: ``participation`` is the mean fraction of devices
    up, ``dark_selected`` the round's count of committee-member-iteration
    pairs that missed, ``staleness_mean``/``staleness_max`` the
    mean/worst staleness of bounded-async stale contributors.
    The robustness fields (DESIGN.md §15.5) are NaN unless corruption
    injection or a robust aggregator is active: ``corrupted_selected`` is
    the round's count of seated-member-iteration pairs whose gradient was
    corrupted (injection ground truth), ``clipped_fraction`` the mean
    fraction of seated members flagged as outliers by the observable signal
    (non-finite or over-norm), ``rollbacks`` the count of group-iteration
    pairs the NaN guard rolled back, and ``agg_residual`` the mean L2
    distance between the robust aggregate and the finite-masked mean (how
    much the robust aggregator actually changed the update).
    The communication fields (DESIGN.md §18.3) account link traffic
    analytically from the compression spec and |θ|: ``bytes_int`` is the
    round's total device↔BS bytes (Eq. 4, download + upload per seated
    contributor over all T iterations), ``bytes_ext`` the BS↔cloud bytes
    (Eq. 5, 2·payload·M), and ``compress_error`` the mean per-transmission
    L2 norm of the error-feedback residual (NaN when compression is off).
    The GBP-CS work counters (reported by the fused and sharded FEDGS
    engines; NaN for baselines and the host loop) sum over the round's T
    iterations: ``gbp_cs_steps`` is the permutation steps GBP-CS took over
    all groups, ``gbp_cs_step_slots`` the steps its group-vmapped
    ``while_loop`` executed (groups × the slowest group's steps, since a
    batched loop runs until its last lane is done); both are 0 on
    iterations that keep the carried committee or select at random, and
    their ratio is how much of the batched solve did useful work.

    Profiler names (DESIGN.md §13.3): the round's phases run under
    ``jax.named_scope`` — ``fedgs.sample``, ``fedgs.schedule``,
    ``fedgs.select``, ``fedgs.train``, ``fedgs.eq4``, ``fedgs.eq5`` and
    ``engine.eval`` — which reach the compiled HLO as ``op_name`` metadata
    only; :func:`records_from_metrics` marks the host's wait for a dispatch
    and its transfer with ``engine.wait`` and ``engine.fetch`` spans.
    """
    round: int
    loss: float
    divergence: float = _NAN
    test_loss: float | None = None
    test_accuracy: float | None = None
    strategy: str = ""
    group_discrepancy: float = _NAN
    selection_distance: float = _NAN
    reselections: float = _NAN
    participation: float = _NAN
    staleness_mean: float = _NAN
    staleness_max: float = _NAN
    dark_selected: float = _NAN
    corrupted_selected: float = _NAN
    clipped_fraction: float = _NAN
    rollbacks: float = _NAN
    agg_residual: float = _NAN
    bytes_int: float = _NAN
    bytes_ext: float = _NAN
    compress_error: float = _NAN
    gbp_cs_steps: float = _NAN
    gbp_cs_step_slots: float = _NAN

    def to_dict(self) -> dict:
        d = dict(self._asdict())
        for k in _OPTIONAL_METRICS:
            if math.isnan(d[k]):          # strategies without the telemetry
                d[k] = None               # (strict-JSON safe, unlike NaN)
        return d


# metric names records_from_metrics forwards to same-named RoundRecord
# fields when an experiment's round_fn reports them (all NaN-defaulted)
_OPTIONAL_METRICS = ("divergence", "group_discrepancy", "selection_distance",
                     "reselections", "participation", "staleness_mean",
                     "staleness_max", "dark_selected", "corrupted_selected",
                     "clipped_fraction", "rollbacks", "agg_residual",
                     "bytes_int", "bytes_ext", "compress_error",
                     "gbp_cs_steps", "gbp_cs_step_slots")


def records_from_metrics(r0: int, metrics: dict, *, strategy: str = ""
                         ) -> list[RoundRecord]:
    """Stacked per-chunk device metrics -> per-round typed records.

    ``metrics`` maps name -> (chunk,) array; recognized names: ``loss``,
    ``test_loss``, ``test_accuracy`` (NaN = no eval that round), and the
    telemetry names in ``_OPTIONAL_METRICS``. The wait for the dispatch
    runs under an ``engine.wait`` profiler span, the transfer and the
    records under ``engine.fetch``.
    """
    with jax.profiler.TraceAnnotation("engine.wait"):
        jax.block_until_ready(metrics)
    with jax.profiler.TraceAnnotation("engine.fetch"):
        host = {k: np.asarray(v, np.float64) for k, v in metrics.items()}
        n = len(next(iter(host.values())))
        recs = []
        for i in range(n):
            tl = host.get("test_loss", [_NAN] * n)[i]
            ta = host.get("test_accuracy", [_NAN] * n)[i]
            recs.append(RoundRecord(
                round=r0 + i,
                loss=float(host["loss"][i]) if "loss" in host else _NAN,
                test_loss=None if math.isnan(tl) else float(tl),
                test_accuracy=None if math.isnan(ta) else float(ta),
                strategy=strategy,
                **{k: float(host[k][i]) for k in _OPTIONAL_METRICS
                   if k in host},
            ))
    return recs


@dataclasses.dataclass(frozen=True)
class Experiment:
    """One federated-learning experiment, engine-agnostic.

    ``round_fn(state, r) -> (state', metrics)`` is the pure body of round
    ``r`` (a traced int32 scalar): client sampling, local training and
    server aggregation all happen on-device; ``metrics`` is a dict of f32
    scalars with a structure that is constant across rounds.

    ``params_fn(state)`` extracts the evaluable global parameters;
    ``eval_fn(params) -> (test_loss, test_accuracy)`` must be jittable
    (device-resident test set — see ``models.cnn.make_eval_fn``) because the
    engine calls it *inside* the round scan.

    ``mesh``/``state_spec`` opt the state into ``shard_map`` execution
    (FEDGS group sharding): ``state_spec`` is a PartitionSpec pytree
    (prefix) for ``state``; metrics and round indices are replicated.
    """
    name: str
    init_state: PyTree
    round_fn: Callable[[PyTree, Array], tuple[PyTree, dict]]
    params_fn: Callable[[PyTree], PyTree]
    eval_fn: Callable[[PyTree], tuple[Array, Array]] | None = None
    mesh: Any = None
    axis_name: str = "groups"
    state_spec: Any = None
    unroll: int = 0   # rounds-scan unroll; 0 = auto (full on CPU, rolled else)


def default_chunk(rounds: int, eval_every: int = 0) -> int:
    """Rounds per host dispatch when the caller doesn't say: align chunks to
    the eval period when there is one, otherwise a modest fixed chunk —
    large enough to amortize dispatch, small enough that the (unrolled on
    CPU, DESIGN.md §7) chunk body compiles quickly."""
    chunk = eval_every if eval_every > 0 else 8
    return max(1, min(chunk, rounds))


def _make_chunk_fn(exp: Experiment, eval_every: int, unroll: int):
    """Build the jitted chunk dispatch: scan of round_fn (+ cond'd eval)
    over a (chunk,) vector of round indices, state donated across
    dispatches. jit re-specializes automatically for a partial last chunk."""

    def body(state, r):
        state, metrics = exp.round_fn(state, r)
        metrics = dict(metrics)
        if exp.eval_fn is not None and eval_every > 0:
            nan2 = (jnp.float32(_NAN), jnp.float32(_NAN))
            with jax.named_scope("engine.eval"):
                tl, ta = jax.lax.cond(
                    (r + 1) % eval_every == 0,
                    lambda p: exp.eval_fn(p),
                    lambda p: nan2,
                    exp.params_fn(state))
            metrics["test_loss"] = jnp.asarray(tl, jnp.float32)
            metrics["test_accuracy"] = jnp.asarray(ta, jnp.float32)
        return state, metrics

    def run_chunk(state, rs):
        length = rs.shape[0]
        if unroll >= length:
            # Fully unrolled chunk: emit the rounds inline with NO scan op.
            # XLA:CPU executes ops inside a rolled loop body single-threaded
            # — even a length-1 scan (DESIGN.md §7) — so the inline form is
            # what keeps per-dispatch compute intra-op parallel on CPU.
            ms = []
            for i in range(length):
                state, m = body(state, rs[i])
                ms.append(m)
            return state, jax.tree.map(lambda *xs: jnp.stack(xs), *ms)
        return jax.lax.scan(body, state, rs, unroll=max(1, unroll))

    if exp.mesh is not None:
        run_chunk = jax.shard_map(
            run_chunk, mesh=exp.mesh,
            in_specs=(exp.state_spec, P()),
            out_specs=(exp.state_spec, P()),
            check_vma=False)
    return jax.jit(run_chunk, donate_argnums=(0,))


def run_experiment(
    exp: Experiment,
    rounds: int,
    *,
    eval_every: int = 0,
    chunk: int = 0,
    log_fn: Callable[[RoundRecord], None] | None = None,
    on_chunk: Callable[[int, int], None] | None = None,
) -> tuple[PyTree, list[RoundRecord]]:
    """Run ``rounds`` federated rounds of ``exp`` in ⌈rounds/chunk⌉ host
    dispatches.

    ``eval_every`` > 0 (with ``exp.eval_fn`` set) evaluates on-device every
    that many rounds inside the scan. ``chunk`` = rounds per dispatch
    (0 = :func:`default_chunk`). ``on_chunk(r0, n)`` fires after each
    dispatch (benchmarks time dispatch boundaries with it).

    Returns (final state, one :class:`RoundRecord` per round).
    """
    eval_on = eval_every if exp.eval_fn is not None else 0
    chunk = chunk or default_chunk(rounds, eval_on)
    chunk = max(1, min(chunk, rounds))
    # XLA:CPU runs rolled scan bodies single-threaded (DESIGN.md §7) — fully
    # unroll the rounds scan there, keep it rolled on accelerators.
    unroll = exp.unroll or (chunk if jax.default_backend() == "cpu" else 1)

    # copy the initial state: the chunk dispatch donates its input buffers,
    # and donating exp.init_state directly would delete caller-owned arrays
    # (warm-start params, a re-run of the same Experiment)
    state = jax.tree.map(lambda leaf: jnp.array(leaf, copy=True),
                         exp.init_state)
    if exp.mesh is not None and exp.state_spec is not None:
        state = jax.device_put(
            state, jax.tree.map(
                lambda spec: NamedSharding(exp.mesh, spec), exp.state_spec,
                is_leaf=lambda x: isinstance(x, P)))
    if exp.eval_fn is not None and eval_every > 0:
        try:   # clear error now instead of a ConcretizationTypeError later:
            jax.eval_shape(exp.eval_fn, exp.params_fn(state))
        except jax.errors.JAXTypeError as e:
            raise TypeError(
                f"Experiment {exp.name!r}: eval_fn is not jittable — the "
                "engine evaluates on-device inside the round scan. Build it "
                "with models.cnn.make_eval_fn (device-resident test set) "
                "instead of a host-loop eval like cnn.evaluate.") from e
    chunk_fn = _make_chunk_fn(exp, eval_on, unroll)
    logs: list[RoundRecord] = []
    r0 = 0
    while r0 < rounds:
        n = min(chunk, rounds - r0)
        rs = r0 + jnp.arange(n, dtype=jnp.int32)
        state, metrics = chunk_fn(state, rs)
        recs = records_from_metrics(r0, metrics, strategy=exp.name)
        logs.extend(recs)
        if log_fn is not None:
            for rec in recs:
                log_fn(rec)
        if on_chunk is not None:
            on_chunk(r0, n)
        r0 += n
    return state, logs


def num_dispatches(rounds: int, chunk: int) -> int:
    """⌈R/chunk⌉ — the host round-trips an experiment costs on this engine."""
    return math.ceil(rounds / max(1, chunk))

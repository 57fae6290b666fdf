"""FEDGS: Federated Group Synchronization — paper Alg. 1.

The simulator vectorizes the hierarchy: groups (factories) are a vmapped
axis of size M; the L selected devices of a group are a second vmapped axis.
One *internal iteration* (Alg. 1 lines 3–8: client selection → local
training → internal synchronization) is a single jitted function; *external
synchronization* (line 10) runs every T iterations.

Workflow equivalence (paper §IV): FEDGS == FedAvg over M homogeneous super
nodes, each running mini-batch SGD with batch nL for T local iterations.
The default train step exploits this directly: ``train_step='grad_avg'``
computes ONE weighted-mean gradient over the (L, n) superbatch and applies
ONE SGD update per group — peak live parameter state is M·|θ|, not M·L·|θ|
(DESIGN.md §11). ``train_step='model_avg'`` keeps the paper's literal
L-one-step-models workflow as the oracle path. ``kernel_backend='pallas'``
routes aggregation and the GBP-CS permutation step through the Pallas
kernels (``core.dispatch``).

Two execution engines share the same math (DESIGN.md §10.1):

* ``run_fedgs`` — the two-phase *host loop*: one Python iteration per
  internal iteration, host-side streams (real FEMNIST / FactoryStreams).
* ``run_fedgs_fused`` — the *device-resident* engine (DESIGN.md §7–§8): all
  T internal iterations of a round fused into one ``lax.scan`` with donated
  buffers, data drawn on-device by a DeviceSampler, and the group axis M
  optionally sharded over a device mesh via ``shard_map`` (external sync
  becomes a pmean across shards).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from . import (compress, dispatch, distributions, engine, gbp_cs, selection,
               sync)

PyTree = Any
Array = jax.Array
LossFn = Callable[[PyTree, Any], Array]


@dataclasses.dataclass(frozen=True)
class FedGSConfig:
    num_groups: int = 10          # M
    devices_per_group: int = 35   # K^m
    num_selected: int = 10        # L
    num_presampled: int = 2       # L_rnd
    iters_per_round: int = 50     # T
    rounds: int = 500             # R
    lr: float = 0.01              # η
    batch_size: int = 32          # n
    num_classes: int = 62         # F
    init: str = gbp_cs.MPINV
    gbp_max_iters: int = 64
    selection: str = "gbp_cs"     # 'gbp_cs' | 'random'
    seed: int = 0
    engine: str = "host"          # 'host' (two-phase loop) | 'fused' (scan)
    scan_unroll: int = 0          # fused scan unroll; 0 = auto (DESIGN.md §7)
    train_step: str = "grad_avg"  # 'grad_avg' (Eq. 4 in gradient space) |
    #                               'model_avg' (oracle: L one-step models)
    kernel_backend: str = "jnp"   # 'jnp' | 'pallas' (core.dispatch)
    force_interpret: bool = False  # pin Pallas interpret mode for heavy ops
    #                               instead of the compiled-aware jnp
    #                               fallback (DESIGN.md §16.2) — parity/CI
    #                               use only; it is ~28× slower on CPU
    reselect_every: int = 1       # GBP-CS cadence in internal iterations:
    #                               1 = every iteration (historical default),
    #                               N = every N iters, 0 = static super nodes
    #                               (select once at t=0; DESIGN.md §13)
    sync: str = "sync"            # availability handling of Eq. 4
    #                               (DESIGN.md §14.3): 'sync' drops missed
    #                               devices (weight 0, committee rebuilt on
    #                               churn); 'bounded_async' keeps them at
    #                               γ^staleness weight via the carried group
    #                               gradient
    gamma: float = 0.5            # bounded_async staleness decay γ ∈ (0, 1]
    max_staleness: int = 4        # bounded_async staleness cap (≥ 1)
    avail_selection: str = "aware"  # 'aware' — GBP-CS sees the up-mask and
    #                               never selects dark devices (DESIGN.md
    #                               §14.2); 'blind' — selection ignores
    #                               availability (the ablation baseline; dark
    #                               picks are dropped or go stale at train
    #                               time, per ``sync``)
    robust_agg: str = "mean"      # Eq. 4 internal aggregation (DESIGN.md
    #                               §15.2): 'mean' (historical, bit-identical)
    #                               | 'clip_norm' | 'trimmed_mean' |
    #                               'coord_median'
    robust_clip: float = 10.0     # clip_norm threshold; also the norm above
    #                               which a member counts as an outlier for
    #                               quarantine/telemetry
    robust_trim: int = 1          # trimmed_mean: members trimmed per side
    quarantine_limit: int = 3     # outlier flags before a device is barred
    #                               from selection (DESIGN.md §15.4); 0 = off
    nan_guard: bool = True        # per-iteration isfinite audit + rollback of
    #                               poisoned group states when corruption is
    #                               injected (DESIGN.md §15.3)
    compress_int: str = "none"    # Eq. 4 internal-sync compression
    #                               (DESIGN.md §18): 'none' | 'topk:FRAC' |
    #                               'int8' | 'topk:FRAC+int8' — applied to
    #                               each group's aggregated gradient with a
    #                               per-group error-feedback residual in the
    #                               scan carry
    compress_ext: str = "none"    # Eq. 5 external-sync compression (same
    #                               grammar): each group's round delta
    #                               ω_t^m − ω_{t-1} is EF-compressed before
    #                               the cloud average

    def __post_init__(self):
        if self.train_step not in ("grad_avg", "model_avg"):
            raise ValueError(f"unknown train_step: {self.train_step!r} "
                             "(expected 'grad_avg' or 'model_avg')")
        if self.reselect_every < 0:
            raise ValueError("reselect_every must be >= 0 (0 = static), got "
                             f"{self.reselect_every}")
        if self.sync not in ("sync", "bounded_async"):
            raise ValueError(f"unknown sync mode: {self.sync!r} "
                             "(expected 'sync' or 'bounded_async')")
        if self.sync == "bounded_async":
            if not 0.0 < self.gamma <= 1.0:
                raise ValueError(f"gamma must be in (0, 1], got {self.gamma}")
            if self.max_staleness < 1:
                raise ValueError("max_staleness must be >= 1, got "
                                 f"{self.max_staleness}")
            if self.train_step == "model_avg":
                raise ValueError(
                    "sync='bounded_async' blends gradients and requires "
                    "train_step='grad_avg' (model_avg has no per-group "
                    "gradient to carry)")
        if self.avail_selection not in ("aware", "blind"):
            raise ValueError(
                f"unknown avail_selection: {self.avail_selection!r} "
                "(expected 'aware' or 'blind')")
        sync.check_robust_agg(self.robust_agg)
        if self.robust_agg != "mean" and self.train_step == "model_avg":
            raise ValueError(
                "robust_agg aggregates the per-member gradient stack and "
                "requires train_step='grad_avg' (model_avg averages models)")
        if self.robust_clip <= 0:
            raise ValueError(f"robust_clip must be > 0, "
                             f"got {self.robust_clip}")
        if self.robust_trim < 0:
            raise ValueError(f"robust_trim must be >= 0, "
                             f"got {self.robust_trim}")
        if self.quarantine_limit < 0:
            raise ValueError("quarantine_limit must be >= 0 (0 = off), got "
                             f"{self.quarantine_limit}")
        dispatch.check_backend(self.kernel_backend)
        ci = compress.parse_compress(self.compress_int)  # raises on bad spec
        compress.parse_compress(self.compress_ext)
        if ci is not None and self.train_step != "grad_avg":
            raise ValueError(
                "compress_int compresses the per-group aggregated gradient "
                "and requires train_step='grad_avg' (model_avg averages "
                "models, not gradients)")

    @property
    def l_sel(self) -> int:
        return self.num_selected - self.num_presampled


class IterationStats(NamedTuple):
    loss: Array          # (M,) mean selected-device loss per group
    divergence: Array    # (M,) || P_t^m − P_real ||
    gbp_iterations: Array  # (M,)


def _gather_selected(tree: PyTree, mask: Array, l: int) -> PyTree:
    """Gather the L selected devices' leading-axis entries (mask has exactly
    L ones) so local training only computes on selected devices. top_k on a
    0/1 mask yields the selected indices in ascending device order (ties
    break toward lower indices), matching the stable argsort it replaces."""
    _, idx = jax.lax.top_k(mask, l)
    return jax.tree.map(lambda leaf: leaf[idx], tree)


def make_fedgs_iteration(loss_fn: LossFn, cfg: FedGSConfig):
    """Build the jitted internal-synchronization iteration (Alg. 1 lines 3–8).

    Returns fn(group_params, key, batches, counts, p_real) ->
    (group_params', IterationStats) where group_params leaves are (M, ...),
    batches leaves are (M, K, n, ...), counts is (M, K, F).
    """

    def per_group(params_m: PyTree, key: Array, batch_m: PyTree,
                  counts_m: Array, p_real: Array):
        # -- Client Selection (line 4)
        if cfg.selection == "gbp_cs":
            sel = selection.select_clients_via_gbp_cs(
                key, counts_m, p_real, cfg.num_selected, cfg.num_presampled,
                init=cfg.init, max_iters=cfg.gbp_max_iters,
                step_fn=dispatch.gbp_step_fn(cfg.kernel_backend))
        else:
            sel = selection.select_clients_random(
                key, counts_m, p_real, cfg.num_selected)
        # -- Local Training + Internal Synchronization (lines 5–8, Eq. 4)
        sel_batches = _gather_selected(batch_m, sel.mask, cfg.num_selected)
        synced, loss = _per_group_train(params_m, sel_batches, loss_fn, cfg)
        return synced, (loss, sel.divergence, sel.iterations)

    @jax.jit
    def iteration(group_params: PyTree, key: Array, batches: PyTree,
                  counts: Array, p_real: Array):
        keys = jax.random.split(key, cfg.num_groups)
        new_params, (loss, div, it) = jax.vmap(
            per_group, in_axes=(0, 0, 0, 0, None))(
                group_params, keys, batches, counts, p_real)
        return new_params, IterationStats(loss, div, it)

    return iteration


@functools.partial(jax.jit, static_argnames=("backend", "force_interpret"))
def external_sync_and_broadcast(group_params: PyTree,
                                backend: str = "jnp",
                                force_interpret: bool = False) -> PyTree:
    """Alg. 1 line 10 (Eq. 5): ω_t = mean_m ω_t^m, then ω_t^m ← ω_t."""
    global_params = dispatch.external_avg_fn(
        backend, force_interpret=force_interpret)(group_params)
    m = jax.tree.leaves(group_params)[0].shape[0]
    broadcast = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (m,) + leaf.shape),
        global_params)
    return broadcast


def replicate_for_groups(params: PyTree, m: int) -> PyTree:
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (m,) + leaf.shape), params)


def global_params(group_params: PyTree) -> PyTree:
    return sync.external_sync(group_params)


def _per_group_train(params_m: PyTree, batches_m: PyTree, loss_fn: LossFn,
                     cfg: FedGSConfig,
                     weights: Array | None = None,
                     grad_tx=None, e: PyTree | None = None,
                     ckey: Array | None = None):
    """Lines 5–8 for one group — shared verbatim by the host loop and the
    fused scan so both engines are numerically interchangeable.

    ``cfg.train_step`` picks the form of Eq. (4) (DESIGN.md §11):

    * ``'model_avg'`` — the paper's literal workflow: one local SGD step on
      each of the L selected devices (vmapped over batches; params are
      closed over, but the L one-step models materialize), then the weighted
      model average.
    * ``'grad_avg'`` — the workflow-equivalent gradient-space form (§IV):
      the weighted mean of per-device gradients is the gradient of the
      weighted mean of per-device losses, so one backward pass over the
      (L, n) superbatch produces the already-averaged gradient and ONE SGD
      update follows — no per-device model (or gradient) stack is ever
      live. With ``kernel_backend='pallas'`` the routing is probed FIRST
      (:func:`dispatch.internal_avg_route`): only when the ``agg_weighted``
      kernel would actually run (compiled / pinned interpret) are the
      per-device gradients materialized and reduced by it; when the
      compiled-aware dispatch would fall back to jnp anyway, the step takes
      the fused single-backward path directly — bit-identical math to the
      jnp backend without paying L backward passes for a reduction that
      never runs as a kernel (the 0.49× linear-leg regression of
      BENCH_fedgs_fused.json, DESIGN.md §16.2).

    ``weights`` are the n^{m,k} internal-sync weights; uniform (paper §V.A)
    if None.

    ``grad_tx`` (with the carried residual ``e`` and a per-group ``ckey``)
    is the §18 internal-sync compression transform
    (:func:`compress.make_grad_tx`): the aggregated gradient is
    EF-compressed before the SGD update and the return value extends to
    ``(params', loss, e', err)``. ``grad_tx=None`` (the default) leaves
    this function literally byte-for-byte the pre-§18 code path.
    """
    if weights is None:
        weights = jnp.ones((cfg.num_selected,), jnp.float32)
    if cfg.train_step == "model_avg":
        dev_step = lambda b: sync.local_step(params_m, b, loss_fn, cfg.lr)
        with jax.named_scope("fedgs.train"):
            new_params, losses = jax.vmap(dev_step)(batches_m)
        with jax.named_scope("fedgs.eq4"):
            synced = dispatch.internal_avg_fn(
                cfg.kernel_backend, force_interpret=cfg.force_interpret)(
                new_params, weights)
            # fault tolerance (DESIGN.md §14.3): a group whose whole
            # committee went dark (all weights 0) keeps its params instead
            # of averaging toward the 1e-12-denominator zero model
            total = jnp.sum(weights)
            synced = jax.tree.map(
                lambda s, p: jnp.where(total > 0, s, p), synced, params_m)
        return synced, jnp.mean(losses)
    if cfg.kernel_backend == "pallas":
        n_params = sum(leaf.size for leaf in jax.tree.leaves(params_m))
        route = dispatch.internal_avg_route(
            "pallas", cfg.num_selected, n_params,
            force_interpret=cfg.force_interpret)
        if route != "jnp":
            with jax.named_scope("fedgs.train"):
                losses, grads = jax.vmap(
                    lambda b: sync.local_grads(params_m, b, loss_fn))(
                        batches_m)
            with jax.named_scope("fedgs.eq4"):
                g = dispatch.internal_avg_fn(
                    "pallas", force_interpret=cfg.force_interpret)(
                        grads, weights)
                if grad_tx is not None:
                    g, e, err = grad_tx(g, e, ckey)
                    return (sync.apply_sgd(params_m, g, cfg.lr),
                            jnp.mean(losses), e, err)
                return sync.apply_sgd(params_m, g, cfg.lr), jnp.mean(losses)
        # route == 'jnp': the kernel would fall back anyway — skip the
        # member-gradient stack and take the fused single-backward below
    wn = weights / jnp.maximum(jnp.sum(weights), 1e-12)

    def weighted_loss(p):
        losses = jax.vmap(lambda b: loss_fn(p, b))(batches_m)
        return jnp.sum(losses * wn), losses

    with jax.named_scope("fedgs.train"):
        (_, losses), g = jax.value_and_grad(weighted_loss, has_aux=True)(
            params_m)
    with jax.named_scope("fedgs.eq4"):
        if grad_tx is not None:
            g, e, err = grad_tx(g, e, ckey)
            return (sync.apply_sgd(params_m, g, cfg.lr), jnp.mean(losses),
                    e, err)
        return sync.apply_sgd(params_m, g, cfg.lr), jnp.mean(losses)


def _per_group_train_avail(params_m: PyTree, batches_m: PyTree,
                           loss_fn: LossFn, cfg: FedGSConfig,
                           fresh_w: Array, stale_sum: Array, g_prev: PyTree,
                           grad_tx=None, e: PyTree | None = None,
                           ckey: Array | None = None):
    """Staleness-bounded Eq. (4) for one group (DESIGN.md §14.3):

        g = Σ_k (w_k/D) g_k + (S/D) ḡ,   D = Σ_k w_k + S,  S = Σ_j γ^{s_j}

    — a single weighted backward over the fresh superbatch (the grad_avg
    trick: ∇ of the w_k/D-weighted loss sum IS the first term), plus the
    carried group gradient ``ḡ = g_prev`` at the stale mass S. Matches
    :func:`sync.bounded_async_sync` without materializing per-device grads.
    At ``S = 0, fresh_w = 1`` every op reduces to the availability-blind
    grad_avg path (÷ same denominator, + S·ḡ/D = + 0·ḡ), and with an
    all-dark committee D's 1e-12 floor yields g = 0 → params unchanged.
    Returns ``(params', mean loss, g)`` — the blend is the next ḡ.

    With ``grad_tx`` (§18 compression) the blended g is EF-compressed
    before the update and the *transmitted* gradient becomes the next ḡ —
    the BS only ever holds what crossed the link — extending the return to
    ``(params', loss, ḡ', e', err)``.
    """
    denom = jnp.maximum(fresh_w.sum() + stale_sum, 1e-12)
    wn = fresh_w / denom

    def weighted_loss(p):
        losses = jax.vmap(lambda b: loss_fn(p, b))(batches_m)
        return jnp.sum(losses * wn), losses

    with jax.named_scope("fedgs.train"):
        (_, losses), g_f = jax.value_and_grad(weighted_loss, has_aux=True)(
            params_m)
    with jax.named_scope("fedgs.eq4"):
        frac = stale_sum / denom
        g = jax.tree.map(lambda gf, gp: gf + frac * gp.astype(jnp.float32),
                         g_f, g_prev)
        if grad_tx is not None:
            g, e, err = grad_tx(g, e, ckey)
            g_out = jax.tree.map(lambda gl, gp: gl.astype(gp.dtype), g,
                                 g_prev)
            return (sync.apply_sgd(params_m, g, cfg.lr), jnp.mean(losses),
                    g_out, e, err)
        g_out = jax.tree.map(lambda gl, gp: gl.astype(gp.dtype), g, g_prev)
        return sync.apply_sgd(params_m, g, cfg.lr), jnp.mean(losses), g_out


def _train_all_groups(gp: PyTree, batches: PyTree, group_loss_fn, cfg:
                      FedGSConfig, weights: Array | None = None,
                      stale_sum: Array | None = None,
                      g_prev: PyTree | None = None,
                      grad_tx=None, e: PyTree | None = None,
                      ckeys: Array | None = None):
    """All-groups superbatch form of the ``grad_avg`` train step
    (DESIGN.md §16.1): ONE backward over a loss summed across every group
    replaces the per-group ``jax.vmap`` of :func:`_per_group_train`.

    ``group_loss_fn(gp, batches) -> (M, L)`` computes every selected
    device's loss with the model's conv/matmul stack flattened over the
    (M·L·n) superbatch in a single dispatch per layer (e.g.
    ``models.cnn.make_group_loss_fn``). Because group g's loss terms depend
    only on ``gp[g]``, the gradient of the summed weighted loss w.r.t. the
    stacked params IS the stack of per-group Eq. (4) gradients — identical
    math to the vmapped path, but XLA:CPU (which single-threads small vmap
    bodies) sees M·L-times-larger ops it can actually parallelize.

    ``weights`` (M, L) are the internal-sync weights (uniform if None);
    with ``stale_sum`` (M,)/``g_prev`` the §14.3 bounded-async blend
    composes exactly as in :func:`_per_group_train_avail`, returning
    ``(gp', (M,) mean loss, ḡ')`` instead of ``(gp', loss)``. ``grad_tx``
    (§18, vmapped over the group axis with per-group residuals ``e`` and
    keys ``ckeys``) appends ``(e', (M,) err)`` to either form.
    """
    m = jax.tree.leaves(gp)[0].shape[0]
    if weights is None:
        weights = jnp.ones((m, cfg.num_selected), jnp.float32)
    denom = weights.sum(-1) + (stale_sum if stale_sum is not None else 0.0)
    denom = jnp.maximum(denom, 1e-12)
    wn = weights / denom[:, None]

    def weighted_loss(p):
        losses = group_loss_fn(p, batches)        # (M, L)
        return jnp.sum(losses * wn), losses

    with jax.named_scope("fedgs.train"):
        (_, losses), g = jax.value_and_grad(weighted_loss, has_aux=True)(gp)
    # the Eq. 4 mean is folded into the weighted loss above, so this scope
    # holds only the blend, the compression and the update
    with jax.named_scope("fedgs.eq4"):
        if stale_sum is None:
            if grad_tx is not None:
                g, e, err = jax.vmap(grad_tx)(g, e, ckeys)
                return (sync.apply_sgd(gp, g, cfg.lr),
                        jnp.mean(losses, axis=-1), e, err)
            return sync.apply_sgd(gp, g, cfg.lr), jnp.mean(losses, axis=-1)
        frac = stale_sum / denom                      # (M,)
        g = jax.tree.map(
            lambda gf, gpv: gf + frac.reshape((m,) + (1,) * (gf.ndim - 1))
            * gpv.astype(jnp.float32), g, g_prev)
        if grad_tx is not None:
            g, e, err = jax.vmap(grad_tx)(g, e, ckeys)
            g_out = jax.tree.map(lambda gl, gpv: gl.astype(gpv.dtype), g,
                                 g_prev)
            return (sync.apply_sgd(gp, g, cfg.lr), jnp.mean(losses, axis=-1),
                    g_out, e, err)
        g_out = jax.tree.map(lambda gl, gpv: gl.astype(gpv.dtype), g, g_prev)
        return (sync.apply_sgd(gp, g, cfg.lr), jnp.mean(losses, axis=-1),
                g_out)


def _check_group_loss_fn(group_loss_fn, cfg: FedGSConfig, robust: bool
                         ) -> bool:
    """Is the §16.1 all-groups path active? Raises on incompatible modes:
    ``model_avg`` has no single backward to fuse, and the robust layer
    needs the materialized per-member gradient stack."""
    if group_loss_fn is None:
        return False
    if cfg.train_step != "grad_avg":
        raise ValueError("group_loss_fn requires train_step='grad_avg' "
                         "(one fused backward; model_avg averages models)")
    if robust:
        raise ValueError(
            "group_loss_fn is incompatible with corruption injection / "
            "robust_agg != 'mean': the robust path needs the per-member "
            "gradient stack (DESIGN.md §15), which the fused all-groups "
            "backward never materializes")
    return True


class AvailStep(NamedTuple):
    """Per-iteration availability bookkeeping (DESIGN.md §14.3); leading
    axes are whatever ``mask``/``avail``/``staleness`` carry (M or none)."""
    fresh_w: Array      # (..., L) internal-sync weights of fresh members
    stale_sum: Array    # (...,)   S = Σ γ^s over this iteration's stale ones
    staleness: Array    # (..., K) advanced clock (post-iteration)
    dark: Array         # (...,)   selected-but-dark count
    stale_mean: Array   # (...,)   mean staleness of the stale contributors
    stale_max: Array    # (...,)   max staleness of the stale contributors


@jax.named_scope("fedgs.schedule")
def _avail_weights(mask: Array, avail: Array, staleness: Array,
                   cfg: FedGSConfig) -> AvailStep:
    """Split the committee into fresh vs stale for one iteration. ``fresh_w``
    rides the ``top_k`` gather order of :func:`_gather_selected` /
    ``DeviceSampler.selected_batch``, so weight i belongs to gathered batch
    i. Uses the PRE-update ``staleness`` for the γ^s mass and telemetry,
    then advances the clock."""
    vals, idx = jax.lax.top_k(mask, cfg.num_selected)
    fresh_w = vals * jnp.take_along_axis(avail, idx, axis=-1)
    stale = mask * (1.0 - avail)
    w = sync.staleness_weights(staleness, cfg.gamma)
    stale_sum = jnp.sum(stale * w, axis=-1)
    s_f = jnp.asarray(staleness, jnp.float32)
    n_stale = jnp.sum(stale, axis=-1)
    stale_mean = jnp.sum(stale * s_f, axis=-1) / jnp.maximum(n_stale, 1.0)
    stale_max = jnp.max(stale * s_f, axis=-1)
    new_staleness = sync.update_staleness(staleness, mask * avail,
                                          cfg.max_staleness)
    return AvailStep(fresh_w, stale_sum, new_staleness, n_stale,
                     stale_mean, stale_max)


class RobustStep(NamedTuple):
    """Per-group outputs of the corruption-exposed train step
    (DESIGN.md §15); member axes follow the ``top_k`` gather order."""
    hit: Array        # (L,) injected-corruption ground truth (telemetry)
    flags: Array      # (L,) observable outliers: non-finite or over-norm
    residual: Array   # () ‖robust aggregate − finite-masked mean‖₂


def _robust_active(cfg: FedGSConfig, corrupt_fn) -> bool:
    """Does this run need the materialized per-member gradient path?"""
    return corrupt_fn is not None or cfg.robust_agg != "mean"


def _per_group_train_robust(params_m: PyTree, batches_m: PyTree,
                            loss_fn: LossFn, cfg: FedGSConfig,
                            weights: Array, t: Array, dev_ids: Array,
                            corrupt_fn, agg_fn,
                            stale_sum: Array | None = None,
                            g_prev: PyTree | None = None,
                            grad_tx=None, e: PyTree | None = None,
                            ckey: Array | None = None):
    """Corruption-exposed Eq. (4) for one group (DESIGN.md §15).

    Unlike the fused-backward ``grad_avg`` path, the L per-member gradients
    are *materialized* (vmapped backward) — both the fault injection (a
    corrupted device emits a corrupted *update*) and the robust aggregators
    (order statistics over the member stack) need the (L, ...) stack. The
    price is L·|θ| live gradient state per group, same as the pallas
    ``grad_avg`` branch.

    With ``stale_sum``/``g_prev`` the §14.3 bounded-async blend composes on
    top: the robust fresh estimate ĝ carries the surviving fresh mass
    W = Σ w·[finite] against the stale mass S, g = (W·ĝ + S·ḡ)/(W + S) —
    at W = Σw (nothing corrupted) this is exactly the §14.3 formula.

    Returns ``(params', mean loss, g_out, RobustStep)``; ``g_out`` is the
    blended gradient (the next ḡ for bounded_async; ignored otherwise).
    ``grad_tx`` (§18) EF-compresses the post-blend gradient — after robust
    aggregation, so the compressor never sees raw corrupted members —
    extending the return with ``(e', err)``.
    """
    with jax.named_scope("fedgs.train"):
        losses, grads = jax.vmap(
            lambda b: sync.local_grads(params_m, b, loss_fn))(batches_m)
    with jax.named_scope("fedgs.schedule"):
        if corrupt_fn is not None:
            grads, hit = corrupt_fn(grads, t, dev_ids)
        else:
            hit = jnp.zeros(weights.shape, jnp.float32)
    with jax.named_scope("fedgs.eq4"):
        finite = sync.member_finite(grads).astype(jnp.float32)
        flags = sync.member_outlier_flags(grads, cfg.robust_clip)
        g = agg_fn(grads, weights)
        if cfg.robust_agg == "mean":
            residual = jnp.float32(0.0)
        else:
            gm = sync.weighted_average(sync._sanitize(grads, finite > 0),
                                       weights * finite)
            residual = jnp.sqrt(sum(
                jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
                for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gm))))
        if stale_sum is not None:
            w_fresh = jnp.sum(weights * finite)
            denom = jnp.maximum(w_fresh + stale_sum, sync.EPS)
            g = jax.tree.map(
                lambda gf, gp: (w_fresh * gf.astype(jnp.float32)
                                + stale_sum * gp.astype(jnp.float32)) / denom,
                g, g_prev)
            if grad_tx is not None:
                g, e, err = grad_tx(g, e, ckey)
            g_out = jax.tree.map(lambda gl, gp: gl.astype(gp.dtype), g,
                                 g_prev)
        else:
            if grad_tx is not None:
                g, e, err = grad_tx(g, e, ckey)
            g_out = g
        step = RobustStep(hit=hit, flags=flags, residual=residual)
        params_new = sync.apply_sgd(params_m, g, cfg.lr)
    if grad_tx is not None:
        return params_new, jnp.mean(losses), g_out, step, e, err
    return params_new, jnp.mean(losses), g_out, step


def _group_finite(tree: PyTree) -> Array:
    """(M,) bool — True where every leaf coordinate of the group is finite
    (leaves carry a leading group axis)."""
    ok = None
    for leaf in jax.tree.leaves(tree):
        f = jnp.all(jnp.isfinite(
            leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)), axis=1)
        ok = f if ok is None else ok & f
    return ok


def _where_groups(pred: Array, new: PyTree, old: PyTree) -> PyTree:
    """Per-group select between two same-structure trees with a leading
    group axis. ``jnp.where(True, new, old)`` returns ``new`` exactly, so
    the all-finite case is bit-identical to no guard at all
    (DESIGN.md §15.3)."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            pred.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)


def make_robust_train_step(loss_fn: LossFn, cfg: FedGSConfig, corrupt_fn, *,
                           bounded: bool = False, grad_tx=None):
    """Jitted robust train step for the two-phase host loop (DESIGN.md §15):
    ``step(gp, batches, fresh_w, t, dev_ids)`` — or with ``bounded``,
    ``step(gp, batches, fresh_w, stale_sum, g_prev, t, dev_ids)`` — vmapping
    :func:`_per_group_train_robust` over groups, with ``t`` a traced scalar
    so one compilation serves every iteration of the fault trace. With
    ``grad_tx`` (§18) every variant takes trailing ``(e, ckeys)`` args and
    returns trailing ``(e', err)``."""
    agg_fn = dispatch.robust_agg_fn(cfg.kernel_backend, cfg.robust_agg,
                                    clip=cfg.robust_clip,
                                    trim=cfg.robust_trim,
                                    force_interpret=cfg.force_interpret)

    if bounded and grad_tx is not None:
        @jax.jit
        def step_async_tx(group_params, batches, fresh_w, stale_sum, g_prev,
                          t, dev_ids, e, ckeys):
            return jax.vmap(
                lambda p, b, w, ss, gpv, di, ev, ck: _per_group_train_robust(
                    p, b, loss_fn, cfg, w, t, di, corrupt_fn, agg_fn,
                    stale_sum=ss, g_prev=gpv, grad_tx=grad_tx, e=ev, ckey=ck)
            )(group_params, batches, fresh_w, stale_sum, g_prev, dev_ids,
              e, ckeys)

        return step_async_tx

    if bounded:
        @jax.jit
        def step_async(group_params, batches, fresh_w, stale_sum, g_prev,
                       t, dev_ids):
            return jax.vmap(
                lambda p, b, w, ss, gpv, di: _per_group_train_robust(
                    p, b, loss_fn, cfg, w, t, di, corrupt_fn, agg_fn,
                    stale_sum=ss, g_prev=gpv)
            )(group_params, batches, fresh_w, stale_sum, g_prev, dev_ids)

        return step_async

    if grad_tx is not None:
        @jax.jit
        def step_tx(group_params, batches, fresh_w, t, dev_ids, e, ckeys):
            return jax.vmap(
                lambda p, b, w, di, ev, ck: _per_group_train_robust(
                    p, b, loss_fn, cfg, w, t, di, corrupt_fn, agg_fn,
                    grad_tx=grad_tx, e=ev, ckey=ck)
            )(group_params, batches, fresh_w, dev_ids, e, ckeys)

        return step_tx

    @jax.jit
    def step(group_params, batches, fresh_w, t, dev_ids):
        return jax.vmap(
            lambda p, b, w, di: _per_group_train_robust(
                p, b, loss_fn, cfg, w, t, di, corrupt_fn, agg_fn)
        )(group_params, batches, fresh_w, dev_ids)

    return step


def make_group_train_step(loss_fn: LossFn, cfg: FedGSConfig, *,
                          availability: bool = False, group_loss_fn=None,
                          grad_tx=None):
    """Train-only half of the iteration (used by the two-phase host loop):
    selected batches (M, L, n, ...) -> internally-synced group params.

    ``availability=True`` returns the weighted form (DESIGN.md §14): for
    ``cfg.sync='sync'`` it is ``step(gp, batches, fresh_w)`` — missed
    devices at weight 0; for ``'bounded_async'`` it is ``step(gp, batches,
    fresh_w, stale_sum, g_prev) -> (gp', loss, g_prev')``.

    ``group_loss_fn`` (requires ``train_step='grad_avg'``) switches every
    variant to the §16.1 all-groups superbatch backward
    (:func:`_train_all_groups`) — same signatures, same math, one fused
    dispatch instead of a vmap of per-group backwards.

    ``grad_tx`` (§18 internal-sync compression) extends every variant with
    trailing ``(e, ckeys)`` args and trailing ``(e', err)`` returns; when
    None the built steps are exactly the pre-§18 callables."""
    grouped = _check_group_loss_fn(group_loss_fn, cfg, False)

    if availability and cfg.sync == "bounded_async":
        if grad_tx is not None:
            @jax.jit
            def step_async_tx(group_params: PyTree, batches: PyTree,
                              fresh_w: Array, stale_sum: Array,
                              g_prev: PyTree, e: PyTree, ckeys: Array):
                if grouped:
                    return _train_all_groups(
                        group_params, batches, group_loss_fn, cfg,
                        weights=fresh_w, stale_sum=stale_sum, g_prev=g_prev,
                        grad_tx=grad_tx, e=e, ckeys=ckeys)
                return jax.vmap(
                    lambda p, b, fw, ss, gp, ev, ck: _per_group_train_avail(
                        p, b, loss_fn, cfg, fw, ss, gp,
                        grad_tx=grad_tx, e=ev, ckey=ck)
                )(group_params, batches, fresh_w, stale_sum, g_prev,
                  e, ckeys)

            return step_async_tx

        @jax.jit
        def step_async(group_params: PyTree, batches: PyTree, fresh_w: Array,
                       stale_sum: Array, g_prev: PyTree):
            if grouped:
                return _train_all_groups(group_params, batches,
                                         group_loss_fn, cfg, weights=fresh_w,
                                         stale_sum=stale_sum, g_prev=g_prev)
            return jax.vmap(
                lambda p, b, fw, ss, gp: _per_group_train_avail(
                    p, b, loss_fn, cfg, fw, ss, gp)
            )(group_params, batches, fresh_w, stale_sum, g_prev)

        return step_async

    if availability:
        if grad_tx is not None:
            @jax.jit
            def step_weighted_tx(group_params: PyTree, batches: PyTree,
                                 fresh_w: Array, e: PyTree, ckeys: Array):
                if grouped:
                    return _train_all_groups(
                        group_params, batches, group_loss_fn, cfg,
                        weights=fresh_w, grad_tx=grad_tx, e=e, ckeys=ckeys)
                return jax.vmap(
                    lambda p, b, w, ev, ck: _per_group_train(
                        p, b, loss_fn, cfg, w, grad_tx, ev, ck)
                )(group_params, batches, fresh_w, e, ckeys)

            return step_weighted_tx

        @jax.jit
        def step_weighted(group_params: PyTree, batches: PyTree,
                          fresh_w: Array):
            if grouped:
                return _train_all_groups(group_params, batches,
                                         group_loss_fn, cfg, weights=fresh_w)
            return jax.vmap(
                lambda p, b, w: _per_group_train(p, b, loss_fn, cfg, w)
            )(group_params, batches, fresh_w)

        return step_weighted

    if grad_tx is not None:
        @jax.jit
        def step_tx(group_params: PyTree, batches: PyTree, e: PyTree,
                    ckeys: Array):
            if grouped:
                return _train_all_groups(group_params, batches,
                                         group_loss_fn, cfg,
                                         grad_tx=grad_tx, e=e, ckeys=ckeys)
            return jax.vmap(
                lambda p, b, ev, ck: _per_group_train(
                    p, b, loss_fn, cfg, None, grad_tx, ev, ck)
            )(group_params, batches, e, ckeys)

        return step_tx

    @jax.jit
    def step(group_params: PyTree, batches: PyTree):
        if grouped:
            return _train_all_groups(group_params, batches, group_loss_fn,
                                     cfg)
        return jax.vmap(
            lambda p, b: _per_group_train(p, b, loss_fn, cfg)
        )(group_params, batches)

    return step


def _compress_specs(cfg: FedGSConfig):
    """(internal, external) parsed §18 compression specs — (None, None) on
    the default config, which every caller treats as 'trace the pre-§18
    graph exactly'."""
    return (compress.parse_compress(cfg.compress_int),
            compress.parse_compress(cfg.compress_ext))


def _compress_carry_index(cfg: FedGSConfig, which: str) -> int:
    """Static position of the §18 EF residual leaves inside the carried
    selection state (see :func:`init_selection_state` for the layout)."""
    spec_int, _ = _compress_specs(cfg)
    base = 4 if cfg.sync == "bounded_async" else 2
    if which == "int":
        return base
    return base + (1 if spec_int is not None else 0)


def _group_params_count(group_params: PyTree) -> int:
    """Per-group |θ| from a group-stacked tree (leaves (M, ...)) — static
    at trace time, the S of the §18 byte accounting."""
    return sum(leaf.size // leaf.shape[0]
               for leaf in jax.tree.leaves(group_params))


def _external_compress(gp0: PyTree, gp: PyTree, e_ext: PyTree, keys: Array,
                       spec, *, backend: str, force_interpret: bool):
    """§18 Eq. 5 compression, delta form: each group transmits
    ``y = C(Δ^m + e^m)`` of its round delta ``Δ^m = ω_t^m − ω_{t-1}`` and
    the cloud averages the reconstructed ``ω_{t-1} + y`` (``gp0`` rows all
    equal the round-entry broadcast model, so the mean telescopes to
    ``ω_{t-1} + mean_m y``). Returns ``(gp_tx, e_ext', (M,) err)``."""
    delta = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), gp, gp0)
    y, e_new, err = jax.vmap(
        lambda d, ev, k: compress.ef_compress(
            d, ev, spec, k, backend=backend,
            force_interpret=force_interpret))(delta, e_ext, keys)
    gp_tx = jax.tree.map(
        lambda b, yv: (b.astype(jnp.float32) + yv.astype(jnp.float32))
        .astype(b.dtype), gp0, y)
    return gp_tx, e_new, err


# The typed per-round log record lives in core.engine and is shared by the
# engine, both host loops, benchmarks and launch/train.py (DESIGN.md §12).
RoundRecord = engine.RoundRecord
RoundLog = engine.RoundRecord  # back-compat alias


def run_fedgs(
    params: PyTree,
    loss_fn: LossFn,
    streams,                     # FactoryStreams-like: next_counts / fetch_selected
    p_real: Array,
    cfg: FedGSConfig,
    *,
    avail_fn=None,
    corrupt_fn=None,
    group_loss_fn=None,
    eval_fn: Callable[[PyTree], tuple[float, float]] | None = None,
    eval_every: int = 10,
    log_fn: Callable[[RoundLog], None] | None = None,
) -> tuple[PyTree, list[RoundLog]]:
    """Alg. 1 end to end — two-phase host loop (DESIGN.md §10.1):

    per iteration: (1) devices report next-batch class counts; (2) the BS
    runs GBP-CS (jitted) to pick C_t^m — every ``cfg.reselect_every``
    iterations; between rebuilds the carried masks are reused and only
    re-scored against the fresh counts (DESIGN.md §13); (3) ONLY the
    selected devices generate/fetch data and take one local SGD step;
    (4) internal sync. External sync every T iterations. ``avail_fn``
    threads an availability schedule through selection and sync — same
    semantics as the fused body (DESIGN.md §14). ``corrupt_fn`` injects
    gradient corruption (``data.streaming.make_corruption_fn``) and —
    together with ``cfg.robust_agg``/``nan_guard``/``quarantine_limit`` —
    activates the robustness layer (DESIGN.md §15): per-member gradients,
    robust Eq. 4, isfinite rollback and selection quarantine.
    ``group_loss_fn`` switches the grad_avg train step to the §16.1
    all-groups superbatch backward (``models.cnn.make_group_loss_fn``) —
    identical math, one fused dispatch per layer across all M·L members.

    With ``cfg.engine == 'fused'`` (or ``'sharded'``, which additionally
    shards the group axis over every available device), dispatches to
    :func:`run_fedgs_fused` — ``streams`` must then be a DeviceSampler
    (DESIGN.md §10.2).
    """
    if cfg.engine in ("fused", "sharded"):
        mesh = make_group_mesh(cfg.num_groups) if cfg.engine == "sharded" \
            else None
        return run_fedgs_fused(params, loss_fn, streams, p_real, cfg,
                               avail_fn=avail_fn, corrupt_fn=corrupt_fn,
                               group_loss_fn=group_loss_fn,
                               mesh=mesh, eval_fn=eval_fn,
                               eval_every=eval_every, log_fn=log_fn)
    if cfg.engine != "host":
        raise ValueError(f"unknown engine: {cfg.engine!r} "
                         "(expected 'host', 'fused', or 'sharded')")
    bounded = cfg.sync == "bounded_async"
    if bounded and avail_fn is None:
        raise ValueError("sync='bounded_async' requires an availability "
                         "schedule (avail_fn)")
    robust = _robust_active(cfg, corrupt_fn)
    if robust and cfg.train_step != "grad_avg":
        raise ValueError("corruption injection requires train_step="
                         "'grad_avg' (the per-member gradient stack)")
    _check_group_loss_fn(group_loss_fn, cfg, robust)
    quarantined = corrupt_fn is not None and cfg.quarantine_limit > 0
    guard = corrupt_fn is not None and cfg.nan_guard
    spec_int, spec_ext = _compress_specs(cfg)
    grad_tx = compress.make_grad_tx(spec_int, backend=cfg.kernel_backend,
                                    force_interpret=cfg.force_interpret)
    if robust:
        train_step = make_robust_train_step(loss_fn, cfg, corrupt_fn,
                                            bounded=bounded, grad_tx=grad_tx)
    else:
        train_step = make_group_train_step(
            loss_fn, cfg, availability=avail_fn is not None,
            group_loss_fn=group_loss_fn, grad_tx=grad_tx)
    gp = replicate_for_groups(params, cfg.num_groups)
    key = jax.random.PRNGKey(cfg.seed)
    p_real = jnp.asarray(p_real, jnp.float32)
    sel_state = init_selection_state(cfg, params)
    mask_c, dist_c = sel_state[0], sel_state[1]
    if bounded:
        staleness, g_prev = sel_state[2], sel_state[3]
    # §18 byte accounting + EF residual state (mirrors the fused carry)
    n_par = sum(leaf.size for leaf in jax.tree.leaves(params))
    payload_int = compress.payload_bytes(n_par, spec_int)
    payload_ext = compress.payload_bytes(n_par, spec_ext)
    e_int = sel_state[_compress_carry_index(cfg, "int")] \
        if spec_int is not None else None
    e_ext = sel_state[_compress_carry_index(cfg, "ext")] \
        if spec_ext is not None else None
    ext_fn = jax.jit(functools.partial(
        _external_compress, spec=spec_ext, backend=cfg.kernel_backend,
        force_interpret=cfg.force_interpret)) if spec_ext is not None \
        else None
    quar = jnp.zeros((cfg.num_groups, cfg.devices_per_group), jnp.int32)
    avail_jit = jax.jit(avail_fn) if avail_fn is not None else None
    flat_ids = jnp.arange(cfg.num_groups * cfg.devices_per_group,
                          dtype=jnp.int32)
    gids = jnp.arange(cfg.num_groups, dtype=jnp.int32)
    # resident population ids (DESIGN.md §17) — same contract as the fused
    # body: DeviceBackedStreams forwards its sampler's `device_ids`;
    # FactoryStreams et al. fall back to the dense arange grid
    ids_fn = getattr(streams, "device_ids", None)
    ids_jit = jax.jit(ids_fn) if ids_fn is not None else None
    logs: list[RoundLog] = []
    t = 0
    for r in range(cfg.rounds):
        losses, divs, discs, dists = [], [], [], []
        parts, darks, smeans, smaxs = [], [], [], []
        corrs, clipfs, rbs, resids = [], [], [], []
        bints, cerrs = [], []
        gp_round0 = gp  # round-entry broadcast model (Δ base for Eq. 5)
        resel = 0
        for _ in range(cfg.iters_per_round):
            key, sub = jax.random.split(key)
            counts = jnp.asarray(streams.next_counts())
            keys = jax.random.split(sub, cfg.num_groups)
            if spec_int is not None:
                # §18 compression keys: folded off the iteration key so the
                # main selection/sampling chain is untouched
                ckeys = jax.random.split(
                    jax.random.fold_in(sub, compress.FOLD_COMPRESS),
                    cfg.num_groups)
            discs.append(float(jnp.mean(
                distributions.group_discrepancy(counts, p_real))))
            if avail_fn is None:
                avail = None
            else:
                ids_t = flat_ids if ids_jit is None else \
                    ids_jit(jnp.int32(t), gids).reshape(-1)
                up, _lat = avail_jit(jnp.int32(t), ids_t)
                avail = up.reshape((cfg.num_groups, cfg.devices_per_group))
            sel_avail = avail if cfg.avail_selection == "aware" else None
            if quarantined:
                ok = selection.quarantine_mask(quar, cfg.quarantine_limit)
                sel_avail = ok if sel_avail is None else sel_avail * ok
            do = bool(selection.reselect_predicate(t, cfg.reselect_every))
            if sel_avail is not None and not bounded \
                    and cfg.reselect_every != 1:
                do = bool(selection.reselect_trigger(
                    do, mask_c, sel_avail, cfg.num_selected))
            if do:
                sel = selection.select_groups_any(
                    keys, counts, p_real, cfg.num_selected,
                    cfg.num_presampled, avail=sel_avail,
                    method=cfg.selection, init=cfg.init,
                    max_iters=cfg.gbp_max_iters,
                    step_fn=dispatch.gbp_step_fn(cfg.kernel_backend))
                mask_c, dist_c, div = sel.mask, sel.distance, sel.divergence
                resel += 1
            else:
                ce = counts if sel_avail is None \
                    else counts * sel_avail[..., None]
                div = distributions.mask_divergence(ce, mask_c, p_real)
            imgs, labs = streams.fetch_selected(np.asarray(mask_c),
                                                cfg.num_selected)
            batches = (jnp.asarray(imgs), jnp.asarray(labs))
            if robust:
                vals, idx = jax.lax.top_k(mask_c, cfg.num_selected)
                if ids_jit is None:
                    dev_ids = (gids[:, None] * cfg.devices_per_group
                               + idx).astype(jnp.int32)
                else:
                    dev_ids = jnp.take_along_axis(
                        ids_jit(jnp.int32(t), gids), idx,
                        axis=-1).astype(jnp.int32)
                if avail is None:
                    fresh_w = vals
                elif bounded:
                    st = _avail_weights(mask_c, avail, staleness, cfg)
                    fresh_w = st.fresh_w
                else:
                    fresh_w = vals * jnp.take_along_axis(avail, idx, axis=-1)
                gp_old = gp
                uploads = float(jnp.sum(fresh_w > 0))
                if bounded:
                    g_prev_old, stale_old = g_prev, staleness
                    if spec_int is not None:
                        e_old = e_int
                        gp, loss, g_prev, rs, e_int, errs = train_step(
                            gp, batches, fresh_w, st.stale_sum, g_prev_old,
                            jnp.int32(t), dev_ids, e_old, ckeys)
                    else:
                        gp, loss, g_prev, rs = train_step(
                            gp, batches, fresh_w, st.stale_sum, g_prev_old,
                            jnp.int32(t), dev_ids)
                    staleness = st.staleness
                elif spec_int is not None:
                    e_old = e_int
                    gp, loss, _g, rs, e_int, errs = train_step(
                        gp, batches, fresh_w, jnp.int32(t), dev_ids,
                        e_old, ckeys)
                else:
                    gp, loss, _g, rs = train_step(gp, batches, fresh_w,
                                                  jnp.int32(t), dev_ids)
                rollbacks = 0.0
                if guard:
                    finite_m = _group_finite(gp)
                    if bounded:
                        finite_m = finite_m & _group_finite(g_prev)
                    if spec_int is not None:
                        finite_m = finite_m & _group_finite(e_int)
                    gp = _where_groups(finite_m, gp, gp_old)
                    if bounded:
                        g_prev = _where_groups(finite_m, g_prev, g_prev_old)
                        staleness = jnp.where(finite_m[:, None],
                                              staleness, stale_old)
                    if spec_int is not None:
                        e_int = _where_groups(finite_m, e_int, e_old)
                    rollbacks = float(jnp.sum(1.0 - finite_m))
                if quarantined:
                    quar = jax.vmap(
                        lambda q, i, f: q.at[i].add(f.astype(jnp.int32))
                    )(quar, idx, rs.flags * vals)
                seated = float(jnp.sum(vals))
                corrs.append(float(jnp.sum(rs.hit * vals)))
                clipfs.append(float(jnp.sum(rs.flags * vals))
                              / max(seated, 1.0))
                rbs.append(rollbacks)
                resids.append(float(jnp.mean(rs.residual)))
                if avail is not None:
                    parts.append(float(jnp.mean(avail)))
                    if bounded:
                        darks.append(float(jnp.sum(st.dark)))
                        smeans.append(float(jnp.mean(st.stale_mean)))
                        smaxs.append(float(jnp.max(st.stale_max)))
                    else:
                        darks.append(float(jnp.sum(mask_c * (1.0 - avail))))
            elif avail is None:
                uploads = float(cfg.num_groups * cfg.num_selected)
                if spec_int is not None:
                    gp, loss, e_int, errs = train_step(gp, batches, e_int,
                                                       ckeys)
                else:
                    gp, loss = train_step(gp, batches)
            elif bounded:
                st = _avail_weights(mask_c, avail, staleness, cfg)
                uploads = float(jnp.sum(st.fresh_w > 0))
                if spec_int is not None:
                    gp, loss, g_prev, e_int, errs = train_step(
                        gp, batches, st.fresh_w, st.stale_sum, g_prev,
                        e_int, ckeys)
                else:
                    gp, loss, g_prev = train_step(gp, batches, st.fresh_w,
                                                  st.stale_sum, g_prev)
                staleness = st.staleness
                darks.append(float(jnp.sum(st.dark)))
                smeans.append(float(jnp.mean(st.stale_mean)))
                smaxs.append(float(jnp.max(st.stale_max)))
                parts.append(float(jnp.mean(avail)))
            else:
                vals, idx = jax.lax.top_k(mask_c, cfg.num_selected)
                fresh_w = vals * jnp.take_along_axis(avail, idx, axis=-1)
                uploads = float(jnp.sum(fresh_w > 0))
                if spec_int is not None:
                    gp, loss, e_int, errs = train_step(gp, batches, fresh_w,
                                                       e_int, ckeys)
                else:
                    gp, loss = train_step(gp, batches, fresh_w)
                darks.append(float(jnp.sum(mask_c * (1.0 - avail))))
                parts.append(float(jnp.mean(avail)))
            bints.append(2.0 * payload_int * uploads)
            if spec_int is not None:
                cerrs.append(float(jnp.mean(errs)))
            losses.append(float(jnp.mean(loss)))
            divs.append(float(jnp.mean(div)))
            dists.append(float(jnp.mean(dist_c)))
            t += 1
        if spec_ext is not None:
            key, esub = jax.random.split(key)
            ekeys = jax.random.split(esub, cfg.num_groups)
            gp_tx, e_ext, err_ext = ext_fn(gp_round0, gp, e_ext, ekeys)
            cerrs.append(float(jnp.mean(err_ext)))
            gp = external_sync_and_broadcast(
                gp_tx, backend=cfg.kernel_backend,
                force_interpret=cfg.force_interpret)
        else:
            gp = external_sync_and_broadcast(
                gp, backend=cfg.kernel_backend,
                force_interpret=cfg.force_interpret)
        tl = ta = None
        if eval_fn is not None and (r + 1) % eval_every == 0:
            tl, ta = eval_fn(global_params(gp))
            tl, ta = float(tl), float(ta)
        log = RoundRecord(
            round=r, loss=float(np.mean(losses)),
            divergence=float(np.mean(divs)),
            test_loss=tl, test_accuracy=ta, strategy="fedgs",
            group_discrepancy=float(np.mean(discs)),
            selection_distance=float(np.mean(dists)),
            reselections=float(resel),
            participation=float(np.mean(parts)) if parts else float("nan"),
            dark_selected=float(np.sum(darks)) if darks else float("nan"),
            staleness_mean=float(np.mean(smeans)) if smeans
            else float("nan"),
            staleness_max=float(np.max(smaxs)) if smaxs else float("nan"),
            corrupted_selected=float(np.sum(corrs)) if corrs
            else float("nan"),
            clipped_fraction=float(np.mean(clipfs)) if clipfs
            else float("nan"),
            rollbacks=float(np.sum(rbs)) if rbs else float("nan"),
            agg_residual=float(np.mean(resids)) if resids
            else float("nan"),
            bytes_int=float(np.sum(bints)),
            bytes_ext=2.0 * payload_ext * cfg.num_groups,
            compress_error=float(np.sum(cerrs) / max(len(cerrs), 1))
            if cerrs else float("nan"))
        logs.append(log)
        if log_fn is not None:
            log_fn(log)
    return global_params(gp), logs


# ---------------------------------------------------------------------------
# Scan-fused, mesh-sharded engine (DESIGN.md §7–§8).
# ---------------------------------------------------------------------------

def _mesh_axis_size(mesh, axis_name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]


def make_group_mesh(num_groups: int | None = None):
    """1-D mesh over the 'groups' axis for the fused engine (DESIGN.md §8):
    each shard simulates M/n_devices super nodes.

    Spans every device of ``jax.devices()`` and raises ``ValueError`` when
    ``num_groups`` does not divide over them — a mesh on fewer devices
    would leave chips idle without saying so. Axes are ``AxisType.Auto``:
    the engine reads the ``P('groups')`` state with plain indexing
    (``params_fn``), which JAX refuses on Explicit axes."""
    n = jax.device_count()
    if num_groups is not None and num_groups % n:
        raise ValueError(
            f"num_groups={num_groups} does not divide over the {n} devices; "
            "choose M as a multiple of the device count")
    return jax.make_mesh((n,), ("groups",), (AxisType.Auto,))


def init_selection_state(cfg: FedGSConfig, params: PyTree | None = None,
                         *, quarantine: bool = False) -> tuple:
    """Initial carried selection state for the round body (DESIGN.md §13):
    ``(mask (M, K), distance (M,))``. All-zero: iteration t=0 always rebuilds
    (``reselect_predicate(0, N)`` is True for every cadence N), so the zeros
    are never trained on. Always full-M — under ``shard_map`` the state is
    sharded by the in_specs/state_spec, not built per shard.

    With ``cfg.sync='bounded_async'`` two more leaves join the carry
    (DESIGN.md §14.3, sharded ``P('groups')`` like the mask): the per-device
    staleness clock ``(M, K) int32``, initialized at ``max_staleness``
    (nobody has ever contributed), and the per-group carried gradient
    ``ḡ (M, |θ|)``, initialized at zero so initial stale mass only damps the
    fresh gradient instead of fabricating an update — ``params`` (the
    zero-template) is required then.

    With compression (``cfg.compress_int`` / ``cfg.compress_ext`` not
    'none', DESIGN.md §18.1) the per-group error-feedback residuals join
    next — ``e_int`` then ``e_ext``, each an ``(M, |θ|)``-shaped f32 params
    tree initialized at zero (nothing has been dropped yet), sharded
    ``P('groups')`` like the carried gradient. ``params`` is required to
    size them. Their static carry indices come from
    :func:`_compress_carry_index`.

    With ``quarantine=True`` (corruption injection + ``quarantine_limit`` >
    0, DESIGN.md §15.4) the per-device outlier-flag counters ``(M, K)
    int32`` join as the LAST leaf — always last, whatever the ``sync`` mode,
    so the round body addresses them as ``sel[-1]``."""
    sel = (jnp.zeros((cfg.num_groups, cfg.devices_per_group), jnp.float32),
           jnp.zeros((cfg.num_groups,), jnp.float32))
    if cfg.sync == "bounded_async":
        if params is None:
            raise ValueError("sync='bounded_async' needs the params template "
                             "to size the carried group gradient")
        staleness = jnp.full((cfg.num_groups, cfg.devices_per_group),
                             cfg.max_staleness, jnp.int32)
        g_prev = replicate_for_groups(
            jax.tree.map(jnp.zeros_like, params), cfg.num_groups)
        sel = sel + (staleness, g_prev)
    spec_int, spec_ext = _compress_specs(cfg)
    if spec_int is not None or spec_ext is not None:
        if params is None:
            raise ValueError("compression needs the params template to size "
                             "the error-feedback residuals")
        zeros = replicate_for_groups(compress.zero_residual(params),
                                     cfg.num_groups)
        if spec_int is not None:
            sel = sel + (zeros,)
        if spec_ext is not None:
            sel = sel + (jax.tree.map(jnp.copy, zeros)
                         if spec_int is not None else zeros,)
    if quarantine:
        sel = sel + (jnp.zeros((cfg.num_groups, cfg.devices_per_group),
                               jnp.int32),)
    return sel


def make_round_body(loss_fn: LossFn, cfg: FedGSConfig, sampler, *,
                    avail_fn=None, corrupt_fn=None, group_loss_fn=None,
                    mesh=None, axis_name: str = "groups"):
    """Build the PURE one-round body of the device-resident engine.

    Returns ``round_body(group_params, key, sel, t0, p_real) ->
    (group_params', key', sel', metrics)`` where ``sel`` is the carried
    selection state — ``(mask (M, K), distance (M,))``, extended with the
    staleness clock and carried group gradient under ``sync='bounded_async'``
    (:func:`init_selection_state`, DESIGN.md §13–§14) — and ``metrics`` maps
    ``loss`` / ``divergence`` / ``group_discrepancy`` /
    ``selection_distance`` / ``reselected`` (plus the §14 availability
    telemetry when ``avail_fn`` is given) to (T,) per-iteration arrays.
    The T internal iterations run as a single ``lax.scan`` (selection →
    local step → internal sync per scan step), with external sync +
    broadcast as the epilogue.

    ``sampler`` is a DeviceSampler (see repro.data.streaming): two pure
    functions of (iteration t, global group ids) — the scan never leaves the
    accelerator for data. Under a drift schedule (DESIGN.md §13) the
    sampler's counts evolve with t and ``cfg.reselect_every`` decides when
    GBP-CS rebuilds the super nodes: cadence 1 (default) keeps the
    historical select-every-iteration path with no ``lax.cond``; any other
    cadence routes through :func:`selection.select_or_keep` (one scalar
    cond around the whole GBP-CS solve).

    ``avail_fn`` is the availability schedule (``data.streaming.
    make_availability_fn``, DESIGN.md §14): a pure fn of (t, flat device
    ids) evaluated on-device each scan step. ``cfg.avail_selection='aware'``
    feeds the up-mask to GBP-CS; ``cfg.sync`` decides whether missed
    committee members are dropped (``'sync'``, with churn-triggered
    reselection) or contribute their γ^staleness-weighted stale gradient
    (``'bounded_async'``).

    ``corrupt_fn`` is the gradient-corruption schedule (``data.streaming.
    make_corruption_fn``, DESIGN.md §15.1) — with it (or with
    ``cfg.robust_agg != 'mean'``) the train step materializes per-member
    gradients, injects the fault trace, and aggregates via
    ``cfg.robust_agg``; ``cfg.nan_guard`` audits each iteration's group
    state with ``jnp.isfinite`` and rolls poisoned groups back to their
    pre-iteration snapshot (a per-group ``jnp.where``, bit-transparent when
    everything is finite); ``cfg.quarantine_limit`` > 0 appends per-device
    outlier counters to the carry (``sel[-1]``) and bars repeat offenders
    from selection like dark devices (DESIGN.md §15.3–§15.4).

    ``group_loss_fn`` (grad_avg only, incompatible with the robust path)
    switches local training to the §16.1 all-groups superbatch backward
    (:func:`_train_all_groups`): one fused (M·L·n) dispatch per layer
    instead of a vmap of per-group backwards — the restructuring that makes
    the CNN round win on XLA:CPU. Under ``shard_map`` the same fn sees the
    shard-local M/n_shards groups; the math is per-group either way.

    With ``mesh``, the body is written for execution *inside* ``shard_map``
    over ``axis_name``: each shard simulates M/n_shards super nodes,
    selection keys are sliced from the *global* key fan-out (so results are
    invariant to the shard count), and external sync completes with a pmean
    across shards. The caller applies ``shard_map`` —
    :func:`make_fused_round` for one jitted round, ``engine.run_experiment``
    for the chunked multi-round scan. ``mesh=None`` is the transparent
    single-device path.
    """
    m, t_per_round, l = cfg.num_groups, cfg.iters_per_round, cfg.num_selected
    k = cfg.devices_per_group
    bounded = cfg.sync == "bounded_async"
    if bounded and avail_fn is None:
        raise ValueError("sync='bounded_async' requires an availability "
                         "schedule (avail_fn)")
    robust = _robust_active(cfg, corrupt_fn)
    if robust and cfg.train_step != "grad_avg":
        raise ValueError("corruption injection requires train_step="
                         "'grad_avg' (the per-member gradient stack)")
    grouped = _check_group_loss_fn(group_loss_fn, cfg, robust)
    quarantined = corrupt_fn is not None and cfg.quarantine_limit > 0
    guard = corrupt_fn is not None and cfg.nan_guard
    agg_fn = dispatch.robust_agg_fn(
        cfg.kernel_backend, cfg.robust_agg, clip=cfg.robust_clip,
        trim=cfg.robust_trim,
        force_interpret=cfg.force_interpret) if robust else None
    # §18: compression specs resolve at trace time — spec None keeps every
    # code path below literally the pre-compression program (no extra PRNG
    # splits, no extra carry leaves), which is what the bit-identity test
    # pins down.
    spec_int, spec_ext = _compress_specs(cfg)
    grad_tx = compress.make_grad_tx(spec_int, backend=cfg.kernel_backend,
                                    force_interpret=cfg.force_interpret)
    i_eint = _compress_carry_index(cfg, "int")
    i_eext = _compress_carry_index(cfg, "ext")
    n_shards = 1 if mesh is None else _mesh_axis_size(mesh, axis_name)
    if m % n_shards != 0:
        raise ValueError(
            f"num_groups={m} must divide over {n_shards} '{axis_name}' shards")
    m_local = m // n_shards
    # lazy/candidate samplers expose the (t, gids) -> (G, K) population-id
    # map; dense samplers predating DESIGN.md §17 may not
    ids_fn = getattr(sampler, "device_ids", None)
    # XLA:CPU runs ops inside a rolled loop body single-threaded, which costs
    # ~3x on the conv train step; fully unrolling the scan restores intra-op
    # parallelism. On accelerators the rolled loop is fine (and compiles T
    # times faster), so auto picks per backend. cfg.scan_unroll overrides.
    unroll = cfg.scan_unroll or (
        t_per_round if jax.default_backend() == "cpu" else 1)

    def round_body(group_params: PyTree, key: Array, sel: tuple,
                   t0: Array, p_real: Array):
        n_par = _group_params_count(group_params)
        payload_int = compress.payload_bytes(n_par, spec_int)
        payload_ext = compress.payload_bytes(n_par, spec_ext)
        if mesh is None:
            gids = jnp.arange(m, dtype=jnp.int32)
        else:
            shard = jax.lax.axis_index(axis_name)
            gids = (shard * m_local
                    + jnp.arange(m_local, dtype=jnp.int32)).astype(jnp.int32)

        def iteration(carry, t):
            gp, key, sel = carry
            mask, dist = sel[0], sel[1]
            with jax.named_scope("fedgs.select"):
                # PRNG discipline identical to the host loop: split the
                # round key, fan out to all M groups, take this shard's
                # slice.
                key, sub = jax.random.split(key)
                keys = jnp.take(jax.random.split(sub, m), gids, axis=0)
            if spec_int is not None:
                # side-chained like the fault/availability streams: fold_in
                # off the round sub-key so the selection PRNG chain is
                # untouched, then the global-fan-out/take slice keeps the
                # stochastic rounding invariant to the shard count
                with jax.named_scope("fedgs.eq4"):
                    csub = jax.random.fold_in(sub, compress.FOLD_COMPRESS)
                    ckeys = jnp.take(jax.random.split(csub, m), gids, axis=0)
            e_int = sel[i_eint] if spec_int is not None else None
            with jax.named_scope("fedgs.sample"):
                counts = sampler.counts(t, gids)
                # Resident ids (DESIGN.md §17): schedules evaluate on the
                # (G, K) flat POPULATION ids of the devices seated this
                # iteration — the sampler's `device_ids` when it draws from
                # a larger universe (lazy population / candidate
                # subsampling), else the historical dense gid·K+slot grid
                # (bit-identical values). Built only when a schedule or the
                # robust path consumes them.
                if avail_fn is not None or robust:
                    if ids_fn is None:
                        dev_ids_all = gids[:, None] * k + jnp.arange(
                            k, dtype=jnp.int32)
                    else:
                        dev_ids_all = ids_fn(t, gids).astype(jnp.int32)
            if avail_fn is None:
                avail = None
            else:
                with jax.named_scope("fedgs.schedule"):
                    up, _lat = avail_fn(t, dev_ids_all.reshape(-1))
                    avail = up.reshape((gids.shape[0], k))
            sel_avail = avail if cfg.avail_selection == "aware" else None
            quar = sel[-1] if quarantined else None
            with jax.named_scope("fedgs.select"):
                if quarantined:
                    # repeat gradient offenders are barred from selection
                    # like dark devices (DESIGN.md §15.4)
                    ok = selection.quarantine_mask(quar, cfg.quarantine_limit)
                    sel_avail = ok if sel_avail is None else sel_avail * ok
                if cfg.reselect_every == 1:
                    res = selection.select_for_groups(
                        keys, counts, p_real, l, cfg.num_presampled,
                        avail=sel_avail, method=cfg.selection, init=cfg.init,
                        max_iters=cfg.gbp_max_iters,
                        step_fn=dispatch.gbp_step_fn(cfg.kernel_backend))
                    mask, div, dist = res.mask, res.divergence, res.distance
                    steps = res.iterations
                    resel = jnp.float32(1.0)
                else:
                    do = selection.reselect_predicate(t, cfg.reselect_every)
                    if sel_avail is not None and not bounded:
                        # churn re-trigger (DESIGN.md §14.2) — psum'd so
                        # every shard takes the same lax.cond branch
                        dark_under = selection.reselect_trigger(
                            do, mask, sel_avail, l)
                        do = dark_under if mesh is None else \
                            jax.lax.psum(dark_under.astype(jnp.float32),
                                         axis_name) > 0
                    mask, div, dist, steps = selection.select_or_keep(
                        do, keys, counts, p_real, l, cfg.num_presampled,
                        prev_mask=mask, prev_distance=dist, avail=sel_avail,
                        method=cfg.selection, init=cfg.init,
                        max_iters=cfg.gbp_max_iters,
                        step_fn=dispatch.gbp_step_fn(cfg.kernel_backend))
                    resel = do.astype(jnp.float32)
                steps, slots = selection.step_counts(steps)
            with jax.named_scope("fedgs.sample"):
                imgs, labs = sampler.selected_batch(t, gids, mask, l)
            extra = {}
            if robust:
                # corruption-exposed path (DESIGN.md §15): materialized
                # per-member gradients, injected fault trace, robust Eq. 4,
                # isfinite rollback, quarantine feedback
                vals, idx = jax.lax.top_k(mask, l)
                dev_ids = jnp.take_along_axis(dev_ids_all, idx, axis=-1)
                if avail is None:
                    fresh_w = vals
                elif bounded:
                    st = _avail_weights(mask, avail, sel[2], cfg)
                    fresh_w = st.fresh_w
                else:
                    with jax.named_scope("fedgs.schedule"):
                        fresh_w = vals * jnp.take_along_axis(avail, idx,
                                                             axis=-1)
                gp_old = gp
                if bounded:
                    g_prev_old = sel[3]
                    if grad_tx is not None:
                        e_old = e_int
                        gp, losses, g_prev, rs, e_int, cerr = jax.vmap(
                            lambda p, b, w, ss, gpv, di, ev, ck:
                            _per_group_train_robust(
                                p, b, loss_fn, cfg, w, t, di, corrupt_fn,
                                agg_fn, stale_sum=ss, g_prev=gpv,
                                grad_tx=grad_tx, e=ev, ckey=ck)
                        )(gp, (imgs, labs), fresh_w, st.stale_sum,
                          g_prev_old, dev_ids, e_int, ckeys)
                    else:
                        gp, losses, g_prev, rs = jax.vmap(
                            lambda p, b, w, ss, gpv, di:
                            _per_group_train_robust(
                                p, b, loss_fn, cfg, w, t, di, corrupt_fn,
                                agg_fn, stale_sum=ss, g_prev=gpv)
                        )(gp, (imgs, labs), fresh_w, st.stale_sum,
                          g_prev_old, dev_ids)
                    staleness = st.staleness
                else:
                    if grad_tx is not None:
                        e_old = e_int
                        gp, losses, _g, rs, e_int, cerr = jax.vmap(
                            lambda p, b, w, di, ev, ck:
                            _per_group_train_robust(
                                p, b, loss_fn, cfg, w, t, di, corrupt_fn,
                                agg_fn, grad_tx=grad_tx, e=ev, ckey=ck)
                        )(gp, (imgs, labs), fresh_w, dev_ids, e_int, ckeys)
                    else:
                        gp, losses, _g, rs = jax.vmap(
                            lambda p, b, w, di: _per_group_train_robust(
                                p, b, loss_fn, cfg, w, t, di, corrupt_fn,
                                agg_fn)
                        )(gp, (imgs, labs), fresh_w, dev_ids)
                rollbacks = jnp.float32(0.0)
                if guard:
                    with jax.named_scope("fedgs.eq4"):
                        finite_m = _group_finite(gp)
                        if bounded:
                            finite_m = finite_m & _group_finite(g_prev)
                        if grad_tx is not None:
                            # a poisoned residual would re-inject the fault
                            # next iteration via error feedback — roll it
                            # back with the group (DESIGN.md §18.1)
                            finite_m = finite_m & _group_finite(e_int)
                        gp = _where_groups(finite_m, gp, gp_old)
                        if bounded:
                            g_prev = _where_groups(finite_m, g_prev,
                                                   g_prev_old)
                            staleness = jnp.where(finite_m[:, None],
                                                  staleness, sel[2])
                        if grad_tx is not None:
                            e_int = _where_groups(finite_m, e_int, e_old)
                        rollbacks = jnp.sum(
                            1.0 - finite_m.astype(jnp.float32))
                sel_new = (mask, dist, staleness, g_prev) if bounded \
                    else (mask, dist)
                if quarantined:
                    with jax.named_scope("fedgs.eq4"):
                        quar_new = jax.vmap(
                            lambda q, i, f: q.at[i].add(f.astype(jnp.int32))
                        )(quar, idx, rs.flags * vals)
                uploads = jnp.sum((fresh_w > 0).astype(jnp.float32))
                seated = jnp.sum(vals)
                extra = {"corrupted_selected": jnp.sum(rs.hit * vals),
                         "clipped_fraction": (jnp.sum(rs.flags * vals)
                                              / jnp.maximum(seated, 1.0)),
                         "rollbacks": rollbacks,
                         "agg_residual": jnp.mean(rs.residual)}
                if avail is not None:
                    extra["participation"] = jnp.mean(avail)
                    if bounded:
                        extra["dark_selected"] = jnp.sum(st.dark)
                        extra["staleness_mean"] = jnp.mean(st.stale_mean)
                        extra["staleness_max"] = jnp.max(st.stale_max)
                    else:
                        extra["dark_selected"] = jnp.sum(
                            mask * (1.0 - avail))
            elif avail is None:
                if grad_tx is not None:
                    if grouped:
                        gp, losses, e_int, cerr = _train_all_groups(
                            gp, (imgs, labs), group_loss_fn, cfg,
                            grad_tx=grad_tx, e=e_int, ckeys=ckeys)
                    else:
                        gp, losses, e_int, cerr = jax.vmap(
                            lambda p, b, ev, ck: _per_group_train(
                                p, b, loss_fn, cfg, None, grad_tx, ev, ck)
                        )(gp, (imgs, labs), e_int, ckeys)
                elif grouped:
                    gp, losses = _train_all_groups(gp, (imgs, labs),
                                                   group_loss_fn, cfg)
                else:
                    gp, losses = jax.vmap(
                        lambda p, b: _per_group_train(p, b, loss_fn, cfg)
                    )(gp, (imgs, labs))
                sel_new = (mask, dist)
                uploads = jnp.float32(gids.shape[0] * l)
            elif bounded:
                st = _avail_weights(mask, avail, sel[2], cfg)
                if grad_tx is not None:
                    if grouped:
                        gp, losses, g_prev, e_int, cerr = _train_all_groups(
                            gp, (imgs, labs), group_loss_fn, cfg,
                            weights=st.fresh_w, stale_sum=st.stale_sum,
                            g_prev=sel[3], grad_tx=grad_tx, e=e_int,
                            ckeys=ckeys)
                    else:
                        gp, losses, g_prev, e_int, cerr = jax.vmap(
                            lambda p, b, fw, ss, gpv, ev, ck:
                            _per_group_train_avail(
                                p, b, loss_fn, cfg, fw, ss, gpv,
                                grad_tx, ev, ck)
                        )(gp, (imgs, labs), st.fresh_w, st.stale_sum,
                          sel[3], e_int, ckeys)
                elif grouped:
                    gp, losses, g_prev = _train_all_groups(
                        gp, (imgs, labs), group_loss_fn, cfg,
                        weights=st.fresh_w, stale_sum=st.stale_sum,
                        g_prev=sel[3])
                else:
                    gp, losses, g_prev = jax.vmap(
                        lambda p, b, fw, ss, gpv: _per_group_train_avail(
                            p, b, loss_fn, cfg, fw, ss, gpv)
                    )(gp, (imgs, labs), st.fresh_w, st.stale_sum, sel[3])
                sel_new = (mask, dist, st.staleness, g_prev)
                uploads = jnp.sum((st.fresh_w > 0).astype(jnp.float32))
                extra = {"participation": jnp.mean(avail),
                         "dark_selected": jnp.sum(st.dark),
                         "staleness_mean": jnp.mean(st.stale_mean),
                         "staleness_max": jnp.max(st.stale_max)}
            else:
                with jax.named_scope("fedgs.schedule"):
                    vals, idx = jax.lax.top_k(mask, l)
                    fresh_w = vals * jnp.take_along_axis(avail, idx, axis=-1)
                if grad_tx is not None:
                    if grouped:
                        gp, losses, e_int, cerr = _train_all_groups(
                            gp, (imgs, labs), group_loss_fn, cfg,
                            weights=fresh_w, grad_tx=grad_tx, e=e_int,
                            ckeys=ckeys)
                    else:
                        gp, losses, e_int, cerr = jax.vmap(
                            lambda p, b, w, ev, ck: _per_group_train(
                                p, b, loss_fn, cfg, w, grad_tx, ev, ck)
                        )(gp, (imgs, labs), fresh_w, e_int, ckeys)
                elif grouped:
                    gp, losses = _train_all_groups(gp, (imgs, labs),
                                                   group_loss_fn, cfg,
                                                   weights=fresh_w)
                else:
                    gp, losses = jax.vmap(
                        lambda p, b, w: _per_group_train(p, b, loss_fn,
                                                         cfg, w)
                    )(gp, (imgs, labs), fresh_w)
                sel_new = (mask, dist)
                uploads = jnp.sum((fresh_w > 0).astype(jnp.float32))
                extra = {"participation": jnp.mean(avail),
                         "dark_selected": jnp.sum(mask * (1.0 - avail))}
            # §18 carry layout: EF residuals slot in after the sync leaves,
            # quarantine counters stay LAST (init_selection_state)
            if spec_int is not None:
                sel_new = sel_new + (e_int,)
                extra["compress_error_int"] = jnp.mean(cerr)
            if spec_ext is not None:
                sel_new = sel_new + (sel[i_eext],)
            if quarantined:
                sel_new = sel_new + (quar_new,)
            # bytes over the BS↔device links this iteration: download +
            # upload per seated contributor (DESIGN.md §18.3) — emitted on
            # the dense path too, so FedAvg-vs-FedGS byte ledgers always
            # compare like for like
            extra["bytes_int"] = 2.0 * payload_int * uploads
            extra["gbp_cs_steps"], extra["gbp_cs_step_slots"] = steps, slots
            disc = jnp.mean(distributions.group_discrepancy(counts, p_real))
            loss, div, d = jnp.mean(losses), jnp.mean(div), jnp.mean(dist)
            if mesh is not None:
                loss = jax.lax.pmean(loss, axis_name)
                div = jax.lax.pmean(div, axis_name)
                disc = jax.lax.pmean(disc, axis_name)
                d = jax.lax.pmean(d, axis_name)
                for name in ("participation", "staleness_mean",
                             "clipped_fraction", "agg_residual",
                             "compress_error_int"):
                    if name in extra:
                        extra[name] = jax.lax.pmean(extra[name], axis_name)
                for name in ("dark_selected", "corrupted_selected",
                             "rollbacks", "bytes_int", "gbp_cs_steps",
                             "gbp_cs_step_slots"):
                    if name in extra:
                        extra[name] = jax.lax.psum(extra[name], axis_name)
                if "staleness_max" in extra:
                    extra["staleness_max"] = jax.lax.pmax(
                        extra["staleness_max"], axis_name)
            met = {"loss": loss, "divergence": div, "group_discrepancy": disc,
                   "selection_distance": d, "reselected": resel, **extra}
            return (gp, key, sel_new), met

        (gp, key, sel), mets = jax.lax.scan(
            iteration, (group_params, key, tuple(sel)),
            t0 + jnp.arange(t_per_round, dtype=jnp.int32), unroll=unroll)
        mets = dict(mets)
        if spec_ext is not None:
            # §18 Eq. 5 compression: each group transmits the compressed
            # round delta against the round-entry broadcast model
            # (group_params — every row identical), with per-group error
            # feedback carried in the selection state at i_eext. Key split
            # only on this path so the 'none' chain stays untouched.
            with jax.named_scope("fedgs.eq5"):
                key, esub = jax.random.split(key)
                ekeys = jnp.take(jax.random.split(esub, m), gids, axis=0)
                gp, e_ext, err_ext = _external_compress(
                    group_params, gp, sel[i_eext], ekeys, spec_ext,
                    backend=cfg.kernel_backend,
                    force_interpret=cfg.force_interpret)
            sel = sel[:i_eext] + (e_ext,) + sel[i_eext + 1:]
            err_ext_m = jnp.mean(err_ext)
            if mesh is not None:
                err_ext_m = jax.lax.pmean(err_ext_m, axis_name)
            mets["compress_error_ext"] = err_ext_m
        # per-round BS↔cloud bytes: download + upload for each of the M
        # base stations (static — Eq. 5 always moves the full payload)
        mets["bytes_ext"] = jnp.float32(2.0 * payload_ext * m)
        # epilogue: external sync (Eq. 5) + broadcast back to the group axis
        with jax.named_scope("fedgs.eq5"):
            g = sync.external_sync_grouped(
                gp, axis_name if mesh is not None else None,
                mean_fn=dispatch.external_avg_fn(
                    cfg.kernel_backend, force_interpret=cfg.force_interpret))
            gp = jax.tree.map(
                lambda leaf: jnp.broadcast_to(leaf[None],
                                              (m_local,) + leaf.shape), g)
        return gp, key, sel, mets

    return round_body


def _selection_state_spec(cfg: FedGSConfig, params: PyTree | None,
                          axis_name: str, *, quarantine: bool = False):
    """PartitionSpec tree matching :func:`init_selection_state`: every leaf
    of the carried selection state — mask, distance, (bounded_async) the
    staleness clock and group gradient, and (corruption) the quarantine
    counters — is sharded over the group axis."""
    template = init_selection_state(cfg, params, quarantine=quarantine)
    return jax.tree.map(lambda _: P(axis_name), template)


def make_fused_round(loss_fn: LossFn, cfg: FedGSConfig, sampler, *,
                     avail_fn=None, corrupt_fn=None, group_loss_fn=None,
                     params: PyTree | None = None,
                     mesh=None, axis_name: str = "groups"):
    """Jitted one-round dispatch over :func:`make_round_body` —
    ``group_params`` buffers are donated, so steady-state rounds allocate
    nothing new. Call as ``fn(gp, key, init_selection_state(cfg[, params],
    quarantine=...), t0, p_real)`` and thread the returned selection state
    into the next round; under ``sync='bounded_async'`` pass the ``params``
    template so the sharding spec covers the extended carry. (The chunked
    multi-round engine wraps the same body via ``make_fedgs_experiment``
    instead.)"""
    fn = make_round_body(loss_fn, cfg, sampler, avail_fn=avail_fn,
                         corrupt_fn=corrupt_fn, group_loss_fn=group_loss_fn,
                         mesh=mesh, axis_name=axis_name)
    if mesh is not None:
        sel_spec = _selection_state_spec(
            cfg, params, axis_name,
            quarantine=corrupt_fn is not None and cfg.quarantine_limit > 0)
        fn = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(axis_name), P(), sel_spec, P(), P()),
            out_specs=(P(axis_name), P(), sel_spec, P()),
            check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def make_fedgs_experiment(
    params: PyTree,
    loss_fn: LossFn,
    sampler,                     # DeviceSampler: counts / selected_batch
    p_real: Array,
    cfg: FedGSConfig,
    *,
    avail_fn=None,
    corrupt_fn=None,
    group_loss_fn=None,
    mesh=None,
    axis_name: str = "groups",
    eval_fn: Callable[[PyTree], tuple[Array, Array]] | None = None,
    unroll: int = 0,
) -> engine.Experiment:
    """FEDGS as an ``engine.Experiment`` (DESIGN.md §12): state is
    (group_params (M, ...), PRNG key, carried selection state (mask,
    distance[, staleness, ḡ][, quarantine] — DESIGN.md §13–§15); one round =
    :func:`make_round_body` at ``t0 = r·T``. ``eval_fn`` must be jittable
    (the engine evaluates inside the round scan — ``models.cnn.
    make_eval_fn``). ``unroll`` controls the engine's rounds-scan unroll
    (0 = auto: full on CPU; 1 = rolled — far cheaper to compile for large
    chunks). ``corrupt_fn`` threads gradient corruption + the robust
    aggregation/guard path through every iteration (DESIGN.md §15)."""
    body = make_round_body(loss_fn, cfg, sampler, avail_fn=avail_fn,
                           corrupt_fn=corrupt_fn, group_loss_fn=group_loss_fn,
                           mesh=mesh, axis_name=axis_name)
    p_real = jnp.asarray(p_real, jnp.float32)
    gp = replicate_for_groups(params, cfg.num_groups)
    quarantined = corrupt_fn is not None and cfg.quarantine_limit > 0
    robust = _robust_active(cfg, corrupt_fn)
    spec_int, spec_ext = _compress_specs(cfg)
    state = (gp, jax.random.PRNGKey(cfg.seed),
             init_selection_state(cfg, params, quarantine=quarantined))
    bounded = cfg.sync == "bounded_async"

    def round_fn(state, r):
        gp, key, sel = state
        gp, key, sel, mets = body(
            gp, key, sel, (r * cfg.iters_per_round).astype(jnp.int32),
            p_real)
        out = {
            "loss": jnp.mean(mets["loss"]),
            "divergence": jnp.mean(mets["divergence"]),
            "group_discrepancy": jnp.mean(mets["group_discrepancy"]),
            "selection_distance": jnp.mean(mets["selection_distance"]),
            "reselections": jnp.sum(mets["reselected"]),
        }
        if avail_fn is not None:
            out["participation"] = jnp.mean(mets["participation"])
            out["dark_selected"] = jnp.sum(mets["dark_selected"])
        if bounded:
            out["staleness_mean"] = jnp.mean(mets["staleness_mean"])
            out["staleness_max"] = jnp.max(mets["staleness_max"])
        if robust:
            out["corrupted_selected"] = jnp.sum(mets["corrupted_selected"])
            out["clipped_fraction"] = jnp.mean(mets["clipped_fraction"])
            out["rollbacks"] = jnp.sum(mets["rollbacks"])
            out["agg_residual"] = jnp.mean(mets["agg_residual"])
        # GBP-CS work counters: permutation steps taken, and the steps the
        # vmapped while_loop executed (groups x the slowest group's steps)
        out["gbp_cs_steps"] = jnp.sum(mets["gbp_cs_steps"])
        out["gbp_cs_step_slots"] = jnp.sum(mets["gbp_cs_step_slots"])
        # §18.3 byte ledger — always emitted (dense numbers when
        # compression is off) so crossover sweeps compare like for like
        out["bytes_int"] = jnp.sum(mets["bytes_int"])
        out["bytes_ext"] = mets["bytes_ext"]
        if spec_int is not None or spec_ext is not None:
            # same estimator as the host loop: mean over every transmission
            # event's per-group ‖e‖₂ — T internal events plus one external
            errs = []
            if spec_int is not None:
                errs.append(jnp.sum(mets["compress_error_int"]))
            if spec_ext is not None:
                errs.append(mets["compress_error_ext"])
            n_ev = (cfg.iters_per_round if spec_int is not None else 0) + \
                (1 if spec_ext is not None else 0)
            out["compress_error"] = sum(errs) / n_ev
        return (gp, key, sel), out

    def params_fn(state):
        # every row of the group axis holds the post-broadcast global model,
        # so row 0 IS ω_t (bit-exact, no re-averaging of identical rows)
        return jax.tree.map(lambda leaf: leaf[0], state[0])

    state_spec = (jax.tree.map(lambda _: P(axis_name), gp), P(),
                  _selection_state_spec(cfg, params, axis_name,
                                        quarantine=quarantined))
    return engine.Experiment(
        name="fedgs" if cfg.selection == "gbp_cs" else "fedgs_random_sel",
        init_state=state, round_fn=round_fn, params_fn=params_fn,
        eval_fn=eval_fn, mesh=mesh, axis_name=axis_name,
        state_spec=state_spec if mesh is not None else None, unroll=unroll)


def run_fedgs_fused(
    params: PyTree,
    loss_fn: LossFn,
    sampler,                     # DeviceSampler: counts / selected_batch
    p_real: Array,
    cfg: FedGSConfig,
    *,
    avail_fn=None,
    corrupt_fn=None,
    group_loss_fn=None,
    mesh=None,
    axis_name: str = "groups",
    eval_fn: Callable[[PyTree], tuple[Array, Array]] | None = None,
    eval_every: int = 10,
    log_fn: Callable[[RoundRecord], None] | None = None,
    chunk: int = 1,
    unroll: int = 0,
) -> tuple[PyTree, list[RoundRecord]]:
    """Alg. 1 end to end on the device-resident engine (DESIGN.md §7, §12).

    Numerically equivalent to :func:`run_fedgs` over a DeviceBackedStreams
    adapter of the same sampler (same PRNG stream discipline, same selection
    and train code paths). ``chunk`` rounds run per host dispatch
    (⌈R/chunk⌉ round-trips; chunk=1 keeps the historical one-dispatch-per-
    round behavior, chunk=0 picks ``engine.default_chunk``). ``eval_fn``
    must be jittable — eval runs on-device inside the round scan at every
    chunk size (see ``models.cnn.make_eval_fn``). ``unroll`` is the
    rounds-scan unroll (0 = auto: full on CPU — right for chunk=1; pass
    unroll=1 for large CPU chunks, where inlining chunk·T round bodies
    would blow up compile time, DESIGN.md §12.2). ``avail_fn`` threads an
    availability schedule through selection and sync (DESIGN.md §14);
    ``corrupt_fn`` threads gradient corruption + the robust aggregation
    path through every iteration (DESIGN.md §15).
    """
    exp = make_fedgs_experiment(params, loss_fn, sampler, p_real, cfg,
                                avail_fn=avail_fn, corrupt_fn=corrupt_fn,
                                group_loss_fn=group_loss_fn,
                                mesh=mesh, axis_name=axis_name,
                                eval_fn=eval_fn, unroll=unroll)
    state, logs = engine.run_experiment(
        exp, cfg.rounds, eval_every=eval_every if eval_fn is not None else 0,
        chunk=chunk, log_fn=log_fn)
    return exp.params_fn(state), logs

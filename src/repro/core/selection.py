"""Select-Clients-Via-GBP-CS (paper Alg. 2 line 1 + Alg. 1 line 4).

Per group m: pre-sample L_rnd devices uniformly (keeps every device's
selection probability nonzero — paper §V.A), build b from the pre-sampled
devices' next-batch counts and A from the remaining candidates, then run
GBP-CS for the remaining L_sel slots. Fully jittable and vmappable over
groups.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import gbp_cs
from .distributions import mask_divergence

Array = jax.Array


class SelectionResult(NamedTuple):
    mask: Array          # (K,) 0/1 over ALL devices of the group (= C_t^m)
    divergence: Array    # || P_t^m - P_real ||_2 of the resulting super node
    distance: Array      # GBP-CS objective || A x - y ||_2
    iterations: Array    # GBP-CS permutation steps taken


def select_clients_via_gbp_cs(
    key: Array,
    counts: Array,            # (K, F) next-batch class counts a_t^{m,k}
    p_real: Array,            # (F,) global class distribution
    l: int,                   # L devices to select in total
    l_rnd: int,               # randomly pre-sampled devices
    *,
    avail: Array | None = None,   # (K,) 0/1 up-mask (DESIGN.md §14.2)
    init: str = gbp_cs.MPINV,
    max_iters: int = 64,
    step_fn=None,
) -> SelectionResult:
    """One group's client selection. K and F are static; jit-friendly.

    With ``avail``, dark devices are never selected (DESIGN.md §14.2):
    their counts are zeroed (they report nothing), the pre-sample
    permutation is stably partitioned so available devices fill the random
    slots first, a repair step swaps any dark GBP-CS picks for the
    best-ranked available candidates, and the final mask is intersected
    with ``avail``. Every step is a no-op at ``avail ≡ 1`` — multiplying
    by 1.0 and stable-sorting equal keys are exact identities — so this
    path is bit-identical to the availability-blind one there.
    """
    k_total, f = counts.shape
    l_sel = l - l_rnd
    counts = jnp.asarray(counts, jnp.float32)
    if avail is not None:
        avail = jnp.asarray(avail, jnp.float32)
        counts = counts * avail[:, None]        # dark devices report nothing

    key_pre, key_opt = jax.random.split(key)
    perm = jax.random.permutation(key_pre, k_total)
    if avail is not None:
        # stable partition: available devices first, permutation order kept
        # within each class (equal keys at avail ≡ 1 leave perm unchanged)
        perm = perm[jnp.argsort(1.0 - avail[perm], stable=True)]
    pre_idx = perm[:l_rnd]                      # C^m_rnd
    cand_idx = perm[l_rnd:]                     # C^m \ C^m_rnd
    pre_mask = jnp.zeros((k_total,), jnp.float32).at[pre_idx].set(1.0)

    b = jnp.sum(counts[pre_idx], axis=0)        # (F,) b_t^m
    A = counts[cand_idx].T                      # (F, K - L_rnd)  A_t^m
    n_total = jnp.sum(counts) / k_total * l     # nL with per-device batch n
    y = n_total * jnp.asarray(p_real, jnp.float32) - b   # Eq. (11)

    res = gbp_cs.gbp_cs_minimize(
        A, y, l_sel, key=key_opt, init=init, max_iters=max_iters,
        step_fn=step_fn,
    )
    x, distance = res.x, res.distance
    if avail is not None:
        # repair: availability dominates the solver's choice — any dark pick
        # is swapped for the best available candidate (chosen-and-up scores
        # 3, up 2, chosen-but-dark 1; stable top-L_sel returns exactly res.x
        # when every chosen candidate is up), then the objective is re-scored
        x = gbp_cs.top_lsel(2.0 * avail[cand_idx] + x, l_sel)
        distance = gbp_cs.objective(A, x, y)
    sel_mask = jnp.zeros((k_total,), jnp.float32).at[cand_idx].set(x)
    mask = pre_mask + sel_mask                  # C_t^m = C_rnd ∪ C_sel (Eq. 18)
    if avail is not None:
        mask = mask * avail                     # invariant: mask ⊆ avail

    divergence = mask_divergence(counts, mask, p_real)
    return SelectionResult(mask=mask, divergence=divergence,
                           distance=distance, iterations=res.iterations)


def select_clients_random(key: Array, counts: Array, p_real: Array,
                          l: int, *,
                          avail: Array | None = None) -> SelectionResult:
    """FedAvg's random selection in the same interface (for baselines)."""
    k_total, _ = counts.shape
    counts = jnp.asarray(counts, jnp.float32)
    perm = jax.random.permutation(key, k_total)
    if avail is not None:
        avail = jnp.asarray(avail, jnp.float32)
        counts = counts * avail[:, None]
        perm = perm[jnp.argsort(1.0 - avail[perm], stable=True)]
    mask = jnp.zeros((k_total,), jnp.float32).at[perm[:l]].set(1.0)
    if avail is not None:
        mask = mask * avail
    divergence = mask_divergence(counts, mask,
                                 jnp.asarray(p_real, jnp.float32))
    return SelectionResult(mask=mask, divergence=divergence,
                           distance=divergence, iterations=jnp.int32(0))


def select_for_groups(keys: Array, counts: Array, p_real: Array, l: int,
                      l_rnd: int, *, avail: Array | None = None,
                      method: str = "gbp_cs",
                      init: str = gbp_cs.MPINV,
                      max_iters: int = 64, step_fn=None) -> SelectionResult:
    """vmap over M groups: keys (M,2), counts (M, K, F), avail (M, K)|None.

    Un-jitted on purpose: this is the selection body shared by the two-phase
    host loop (which jits it via :func:`select_groups_any`) and the fused
    scan loop (which traces it inside ``lax.scan``, DESIGN.md §10.1) — one
    code path, so both engines compute bit-for-bit the same masks.

    ``step_fn`` swaps the GBP-CS permutation step (e.g. the Pallas
    ``kernels.gbp_cs.ops.fused_step`` via ``core.dispatch.gbp_step_fn``);
    it is forwarded untouched to :func:`gbp_cs.gbp_cs_minimize`.
    """
    if method == "gbp_cs":
        fn = lambda k, c, a: select_clients_via_gbp_cs(
            k, c, p_real, l, l_rnd, avail=a, init=init, max_iters=max_iters,
            step_fn=step_fn)
    elif method == "random":
        fn = lambda k, c, a: select_clients_random(k, c, p_real, l, avail=a)
    else:
        raise ValueError(f"unknown selection method: {method!r}")
    if avail is None:
        return jax.vmap(lambda k, c: fn(k, c, None))(keys, counts)
    return jax.vmap(fn)(keys, counts, avail)


select_groups_any = functools.partial(
    jax.jit,
    static_argnames=("l", "l_rnd", "method", "init", "max_iters", "step_fn")
)(select_for_groups)


def step_counts(iterations: Array) -> tuple[Array, Array]:
    """GBP-CS work of one selection over M groups, from the per-group
    permutation steps ``iterations`` (M,): ``(steps, slots)`` as float32,
    where ``steps`` is their sum and ``slots`` the steps the vmapped
    ``while_loop`` executed — M times the largest group's steps, since a
    batched loop runs until its last lane is done."""
    it = jnp.asarray(iterations, jnp.float32)
    return jnp.sum(it), it.shape[0] * jnp.max(it)


def quarantine_mask(quarantine: Array, limit: int) -> Array:
    """Selection eligibility from per-device quarantine counters
    (DESIGN.md §15.4): a device flagged as a gradient outlier ``limit`` or
    more times is barred from GBP-CS exactly like a dark device — callers
    fold the returned 0/1 mask into the ``avail`` argument of the selection
    functions, so repeat offenders are never seated again (counts zeroed,
    repair step swaps them out, final mask intersected). ``limit <= 0``
    disables quarantine (all-ones mask). Shapes pass through: (K,) or
    (M, K) counters give a same-shaped mask."""
    q = jnp.asarray(quarantine, jnp.float32)
    if limit <= 0:
        return jnp.ones_like(q)
    return (q < limit).astype(jnp.float32)


def reselect_predicate(t: Array, reselect_every: int) -> Array:
    """When does iteration ``t`` rebuild the super nodes (DESIGN.md §13)?

    ``reselect_every = N >= 1`` → every N internal iterations (N=1 is the
    historical select-every-iteration cadence); ``0`` → static super nodes
    (selection runs once, at t=0, and is carried forever). Shared by the
    host loop (a Python bool on a concrete t) and the fused scan (a traced
    predicate feeding ``lax.cond``), so both engines rebuild on exactly the
    same iterations.
    """
    if reselect_every == 0:
        return t == 0
    return t % reselect_every == 0


def reselect_trigger(do_reselect: Array, mask: Array, avail: Array,
                     l: int) -> Array:
    """Availability re-trigger for ``sync='sync'`` committees (DESIGN.md
    §14.2): force a rebuild when any carried-committee member went dark, or
    when any committee is under-strength (fewer than ``l`` members — the
    aftermath of an infeasible rebuild, retried until devices return).

    Returns a scalar predicate; under shard_map callers must ``psum`` the
    per-shard counts first so every shard takes the same ``lax.cond`` branch
    — this helper is pure local math, the collective stays at the call site.
    """
    dark = jnp.sum(mask * (1.0 - avail))
    under = jnp.sum(jnp.maximum(l - jnp.sum(mask, axis=-1), 0.0))
    return jnp.logical_or(do_reselect, (dark + under) > 0)


def select_or_keep(do_reselect: Array, keys: Array, counts: Array,
                   p_real: Array, l: int, l_rnd: int, *,
                   prev_mask: Array, prev_distance: Array,
                   avail: Array | None = None,
                   method: str = "gbp_cs", init: str = gbp_cs.MPINV,
                   max_iters: int = 64, step_fn=None
                   ) -> tuple[Array, Array, Array, Array]:
    """Periodic in-scan reselection: run GBP-CS for all M groups, or keep
    the carried masks, behind ONE scalar ``lax.cond`` (DESIGN.md §13).

    The cond sits *outside* the group vmap — the cadence predicate is global,
    so on skip iterations the whole GBP-CS solve (the expensive branch) is
    never executed; the cheap branch only re-scores the carried mask against
    the CURRENT counts (``mask_divergence`` — under drift the carried
    committee's divergence degrades, which is the telemetry that makes
    staleness visible).

    With ``avail`` the fresh branch runs availability-aware selection; the
    keep branch re-scores against availability-masked counts but carries the
    FULL committee mask — a dark member is not evicted here (in
    ``bounded_async`` it keeps contributing its stale gradient, and in
    ``sync`` mode :func:`reselect_trigger` folds churn into ``do_reselect``
    so this cond rebuilds instead of keeping).

    Returns ``(mask (M, K), divergence (M,), distance (M,), iterations
    (M,))``; distance is the GBP-CS objective of the LAST rebuild (carried
    through skips), iterations the permutation steps taken (0 on a keep).
    """

    def fresh(_):
        sel = select_for_groups(keys, counts, p_real, l, l_rnd, avail=avail,
                                method=method, init=init,
                                max_iters=max_iters, step_fn=step_fn)
        return sel.mask, sel.divergence, sel.distance, sel.iterations

    def keep(_):
        c = counts if avail is None else counts * avail[..., None]
        div = mask_divergence(c, prev_mask, p_real)
        return (prev_mask, div, prev_distance,
                jnp.zeros(prev_distance.shape, jnp.int32))

    return jax.lax.cond(do_reselect, fresh, keep, None)

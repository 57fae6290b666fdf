"""Pallas TPU kernel: robust (order-statistics) internal aggregation.

The Eq. 4 robust aggregators (DESIGN.md §15.2) need per-coordinate order
statistics over the K-member gradient stack — trimmed mean and coordinate
median — which the plain ``agg_weighted`` matmul kernel cannot express. This
kernel computes them per (K × BP) VMEM tile with a *rank-selection* scheme
instead of a sort: pairwise compares give each member's rank per coordinate
(ties broken by member index, a strict total order), and the trim window /
median picks are rank tests — elementwise compares and reductions only, so
the same body lowers on TPU (no sort primitive inside the kernel) and runs
under interpret mode on CPU. The K² compares run as K (K × BP) tiles, one
per member j (K committee members × a 512-wide parameter block).

Inactive members (weight 0 or non-finite — the ops wrapper computes the
mask) are pushed to +max so their ranks land past every active member's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIG = 3.0e38  # below f32 max: +max itself would overflow the compares


def _make_kernel(method: str, trim: int, k: int):
    def kern(a_ref, x_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)               # (K, BP)
        active = jnp.broadcast_to(a_ref[...] > 0, x.shape)  # (K,1) → (K,BP)
        v = jnp.where(active, x, jnp.float32(_BIG))
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        # rank[k, c] = #{j : v[j, c] < v[k, c], ties by j < k} — a strict
        # total order, so active ranks are exactly 0..n-1 per coordinate.
        # One (K, BP) compare per member j: the TPU compiler takes no 3-D
        # (K, K, BP) broadcast here.
        rank = jnp.zeros(x.shape, jnp.int32)
        for j in range(k):
            vj = v[j:j + 1, :]                           # (1, BP)
            rank += ((vj < v) | ((vj == v) & (j < row))).astype(jnp.int32)
        n = jnp.sum(active.astype(jnp.int32), axis=0, keepdims=True)
        if method == "trimmed_mean":
            t_eff = jnp.minimum(jnp.int32(trim),
                                jnp.maximum((n - 1) // 2, 0))
            inc = active & (rank >= t_eff) & (rank < n - t_eff)
            cnt = jnp.maximum(n - 2 * t_eff, 1).astype(jnp.float32)
            out = jnp.sum(jnp.where(inc, v, 0.0), axis=0,
                          keepdims=True) / cnt
        else:  # coord_median
            lo = jnp.maximum((n - 1) // 2, 0)
            hi = n // 2
            pick_lo = jnp.sum(jnp.where(active & (rank == lo), v, 0.0),
                              axis=0, keepdims=True)
            pick_hi = jnp.sum(jnp.where(active & (rank == hi), v, 0.0),
                              axis=0, keepdims=True)
            out = (pick_lo + pick_hi) * 0.5
        o_ref[...] = jnp.where(n > 0, out, 0.0)

    return kern


@functools.partial(jax.jit,
                   static_argnames=("method", "trim", "block_p", "interpret"))
def robust_agg_kernel(stacked: jax.Array, active: jax.Array, *,
                      method: str, trim: int = 1, block_p: int = 512,
                      interpret: bool = True) -> jax.Array:
    """stacked (K, P) f32, active (K,) 0/1 — P must be a multiple of
    block_p. Returns the (P,) per-coordinate robust aggregate."""
    k, p = stacked.shape
    assert p % block_p == 0
    return pl.pallas_call(
        _make_kernel(method, trim, k),
        grid=(p // block_p,),
        in_specs=[
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
        name="robust_agg",
    )(active.astype(jnp.float32)[:, None], stacked)[0]

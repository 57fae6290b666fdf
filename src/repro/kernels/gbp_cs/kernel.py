"""Pallas TPU kernels for the GBP-CS permutation step (paper Alg. 2 lines 5-8).

TPU adaptation (DESIGN.md §5): one permutation step is two fused stages —

  residual_kernel:  r = A x − y and d² = ‖r‖²   (grid over K blocks,
                    accumulating partial mat-vecs in a VMEM scratch)
  select_kernel:    g = Aᵀ r per block; running masked argmin over x=0 and
                    argmax over x=1 carried across the sequential grid in
                    a lane-aligned (1, 128) output row → the swap pair
                    (i_{0→1}, i_{1→0}).

F (number of classes, ≤ a few hundred) is padded to the 128-lane register
width; K (candidate devices) is tiled BK at a time. The data-dependent outer
loop (repeat until d stops decreasing) stays a lax.while_loop on the scalar
core — there is no TPU analogue of dynamic device-side loop spawning, nor is
one needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.4e38
POS = 3.4e38
LANES = 128


def _residual_kernel(x_ref, a_ref, y_ref, r_ref, d_ref, *, nk: int):
    """Grid (nk,): accumulate r += A_blk @ x_blk; finish with r -= y, d=‖r‖².

    r is carried as an (F, 1) column so both products are lane/sublane
    reductions of a broadcast — no transposes, no scalar stores (the TPU
    compiler refuses scalar stores to VMEM)."""
    ik = pl.program_id(0)

    @pl.when(ik == 0)
    def _init():
        r_ref[...] = jnp.zeros_like(r_ref)

    a = a_ref[...]                       # (F, BK)
    x = x_ref[...]                       # (1, BK)
    r_ref[...] += jnp.sum(a * x, axis=1, keepdims=True)  # (F, 1)

    @pl.when(ik == nk - 1)
    def _finish():
        r = r_ref[...] - y_ref[...]
        r_ref[...] = r
        d_ref[...] = jnp.sum(r * r, axis=(0, 1), keepdims=True)


def _select_kernel(r_ref, a_ref, x_ref, best_ref, *, nk: int, bk: int,
                   k_valid: int):
    """Grid (nk,): g_blk = A_blkᵀ r; carry the running (min g | x=0, idx)
    and (max g | x=1, idx) in lanes 0-3 of a (1, 128) output row across the
    sequential grid. Every step is a vector op: argmin/argmax are a min
    over the lanes holding the extreme value (the first index wins, as in
    ``jnp.argmin``), and a block replaces the carry only on a strict
    improvement, so ties keep the earlier block."""
    ik = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, best_ref.shape, 1)

    @pl.when(ik == 0)
    def _init():
        best_ref[...] = jnp.where(lane == 0, POS,
                                  jnp.where(lane == 2, NEG, -1.0))

    a = a_ref[...]                                       # (F, BK)
    g = jnp.sum(a * r_ref[...], axis=0, keepdims=True)   # (1, BK) = A_blkᵀ r
    x = x_ref[...]                                       # (1, BK)
    idx = (ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
           ).astype(jnp.float32)
    valid = idx < k_valid
    g0 = jnp.where((x < 0.5) & valid, g, POS)
    g1 = jnp.where((x > 0.5) & valid, g, NEG)
    m0 = jnp.min(g0, axis=1, keepdims=True)              # (1, 1)
    m1 = jnp.max(g1, axis=1, keepdims=True)
    i0 = jnp.min(jnp.where(g0 == m0, idx, POS), axis=1, keepdims=True)
    i1 = jnp.min(jnp.where(g1 == m1, idx, POS), axis=1, keepdims=True)
    best = best_ref[...]                                 # (1, 128)
    b0 = jnp.sum(jnp.where(lane == 0, best, 0.0), axis=1, keepdims=True)
    b2 = jnp.sum(jnp.where(lane == 2, best, 0.0), axis=1, keepdims=True)
    up0, up1 = m0 < b0, m1 > b2
    new = jnp.where(lane == 0, m0, jnp.where(lane == 1, i0,
                    jnp.where(lane == 2, m1, i1)))
    best_ref[...] = jnp.where(((lane < 2) & up0) | ((lane >= 2) & (lane < 4)
                                                    & up1), new, best)


def residual(A: jax.Array, x: jax.Array, y: jax.Array, *, bk: int = 128,
             interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """r = A x − y (padded shapes), d² = ‖r‖². A (F, Kp), x (Kp,), y (F,)."""
    f, kp = A.shape
    nk = kp // bk
    r, d2 = pl.pallas_call(
        functools.partial(_residual_kernel, nk=nk),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((1, bk), lambda i: (0, i)),
            pl.BlockSpec((f, bk), lambda i: (0, i)),
            pl.BlockSpec((f, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((f, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((f, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gbp_cs",
    )(x[None], A, y[:, None])
    return r[:, 0], d2[0, 0]


def select_swap(A: jax.Array, x: jax.Array, r: jax.Array, *, k_valid: int,
                bk: int = 128, interpret: bool = True
                ) -> tuple[jax.Array, jax.Array]:
    """Swap pair (i_{0→1}, i_{1→0}) from the gradient g = Aᵀ r̂ (Eq. 15-16)."""
    f, kp = A.shape
    nk = kp // bk
    best = pl.pallas_call(
        functools.partial(_select_kernel, nk=nk, bk=bk, k_valid=k_valid),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((f, 1), lambda i: (0, 0)),
            pl.BlockSpec((f, bk), lambda i: (0, i)),
            pl.BlockSpec((1, bk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        interpret=interpret,
        name="gbp_cs",
    )(r[:, None], A, x[None])
    return best[0, 1].astype(jnp.int32), best[0, 3].astype(jnp.int32)

"""Pallas TPU kernel: top-k magnitude selection via pairwise ranks.

The §18 sparsifier needs "keep the k largest-|x| of P coordinates" with a
deterministic tie order, but Pallas has no sort/top_k primitive — so like
the ``robust_agg`` order-statistics kernel (DESIGN.md §15.2) it computes
each coordinate's *rank* by pairwise compares against the whole vector and
keeps rank < k:

    rank_i = #{ j : |x_j| > |x_i|  or  (|x_j| == |x_i| and j < i) }

— a strict total order (ties broken toward the lower index, matching the
stable ``jax.lax.top_k`` reference bit-for-bit). The grid walks BP-wide
blocks of the output; each program compares its block against the full
vector, an O(P·BP) tile of elementwise compares — O(P²) total, which is
why the ops wrapper routes heavy sizes through the compiled-aware
``route_op`` (the jnp reference is one real ``top_k``).

Zero padding (the ops wrapper pads P up to the block size) is rank-safe:
padded entries sit at the highest indices with magnitude 0, so they rank
*after* every real coordinate — including real zeros — and can never
displace one from the top-k window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(k_keep: int, block_p: int):
    def kern(xb_ref, xf_ref, o_ref):
        i = pl.program_id(0)
        xb = xb_ref[...].astype(jnp.float32)[0]          # (BP,) block
        xf = xf_ref[...].astype(jnp.float32)[0]          # (P,) full vector
        mb, mf = jnp.abs(xb), jnp.abs(xf)
        n = xf.shape[0]
        # global index of each block row / each compared column
        jb = (i * block_p
              + jax.lax.broadcasted_iota(jnp.int32, (block_p, n), 0))
        jf = jax.lax.broadcasted_iota(jnp.int32, (block_p, n), 1)
        gt = mf[None, :] > mb[:, None]
        eq = mf[None, :] == mb[:, None]
        rank = jnp.sum((gt | (eq & (jf < jb))).astype(jnp.int32), axis=1)
        o_ref[...] = jnp.where(rank < k_keep, xb, 0.0)[None]

    return kern


@functools.partial(jax.jit,
                   static_argnames=("k", "block_p", "interpret"))
def topk_select_kernel(x: jax.Array, *, k: int, block_p: int = 512,
                       interpret: bool = True) -> jax.Array:
    """x (P,) f32 with P a multiple of block_p — returns x with everything
    but the k lowest-rank (largest-magnitude) coordinates zeroed."""
    (p,) = x.shape
    assert p % block_p == 0
    return pl.pallas_call(
        _make_kernel(k, block_p),
        grid=(p // block_p,),
        in_specs=[
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
        name="topk_compress",
    )(x[None], x[None])[0]

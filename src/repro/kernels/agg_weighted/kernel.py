"""Pallas TPU kernel: BS-side weighted model aggregation (paper Eqs. 4/5).

ω_t^m = Σ_k (n^{m,k}/n^m) ω_t^{m,k} over K stacked client models, fused as a
blocked weighted reduction over the flattened-parameter axis: each grid step
loads a (K × BP) tile of stacked params into VMEM and emits the (BP,)
weighted sum — one HBM pass over the client models instead of K separate
scale+add passes (what a naive tree_map produces on the aggregation server).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _agg_kernel(w_ref, x_ref, o_ref):
    w = w_ref[...]                      # (1, K)
    x = x_ref[...]                      # (K, BP)
    o_ref[...] = (w @ x.astype(jnp.float32)).astype(o_ref.dtype)


def agg_weighted_kernel(stacked: jax.Array, weights: jax.Array, *,
                        block_p: int = 512, interpret: bool = True
                        ) -> jax.Array:
    """stacked (K, P), weights (K,) — P must be a multiple of block_p."""
    k, p = stacked.shape
    assert p % block_p == 0
    return pl.pallas_call(
        _agg_kernel,
        grid=(p // block_p,),
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
        name="agg_weighted",
    )(weights[None], stacked)[0]

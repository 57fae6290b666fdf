"""Pallas TPU kernel: fused im2col-matmul conv block (DESIGN.md §16.1).

One grid step computes a (rows × Qp)·(Qp × Cp) tile of the im2col matmul on
the MXU and applies the whole epilogue — bias add, ReLU, and the
non-overlapping 2×2 maxpool — on the VPU before anything returns to HBM:
the pre-activation tile ``y`` (the backward's ReLU/pool mask residual) and
the block output are the only writes. The second grid axis walks row
blocks, the first walks groups (per-group weights — this is the (M·L·n)
conv superbatch of the FEDGS round collapsed into ONE kernel launch).

With ``pool`` the ops wrapper hands the patches in *pool-major* row order,
(G, 4, R/4, Qp): slab ``d`` of axis 1 holds the im2col rows of window
member d = 2·dy + dx for every pooled output position (image, row/2,
col/2). The pool is then the max of the four slabs' rows, row for row — no
reshape and no strided access inside the kernel, and any row block that is
a multiple of the 8-row f32 sublane is legal (the TPU compiler takes blocks
whose last two dims are multiples of (8, 128)).

Qp (im2col features, k²·Cin) and Cp (output channels) are padded to the
128-lane MXU width by the ops wrapper; zero feature columns and zero weight
rows contribute nothing to the matmul, and padded output channels are
sliced off outside.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_pool_kernel(x_ref, w_ref, b_ref, o_ref, y_ref):
    w = w_ref[0]                                   # (Qp, Cp)
    b = b_ref[0]                                   # (1, Cp)
    out = None
    for d in range(4):                             # the 2×2 window members
        y = jnp.dot(x_ref[0, d], w, preferred_element_type=jnp.float32) + b
        y_ref[0, d] = y.astype(y_ref.dtype)
        out = y if out is None else jnp.maximum(out, y)
    o_ref[0] = jnp.maximum(out, 0.0).astype(o_ref.dtype)   # ReLU∘max


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, y_ref):
    y = jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32) \
        + b_ref[0]
    y_ref[0] = y.astype(y_ref.dtype)
    o_ref[0] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def conv_fused_kernel(patches: jax.Array, w: jax.Array, bias: jax.Array, *,
                      block_r: int, interpret: bool = True
                      ) -> tuple[jax.Array, jax.Array]:
    """w (G, Qp, Cp); bias (G, 1, Cp); ``patches`` either (G, Rp, Qp) —
    no pool — or pool-major (G, 4, Rp, Qp). Returns ``(out, y)`` with
    ``out`` (G, Rp, Cp) — ReLU, pooled over axis 1 when the patches are
    pool-major — and ``y`` the pre-activation (backward residual) in the
    patches' row layout. Rp must divide by ``block_r``, a multiple of 8."""
    g, *lead, rp, qp = patches.shape
    cp = w.shape[-1]
    assert rp % block_r == 0 and block_r % 8 == 0, (rp, block_r)
    pool = bool(lead)
    blk = (1, *lead, block_r)
    zero = (0,) * len(lead)
    return pl.pallas_call(
        _conv_pool_kernel if pool else _conv_kernel,
        grid=(g, rp // block_r),
        in_specs=[
            pl.BlockSpec((*blk, qp), lambda ig, ir: (ig, *zero, ir, 0)),
            pl.BlockSpec((1, qp, cp), lambda ig, ir: (ig, 0, 0)),
            pl.BlockSpec((1, 1, cp), lambda ig, ir: (ig, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r, cp), lambda ig, ir: (ig, ir, 0)),
            pl.BlockSpec((*blk, cp), lambda ig, ir: (ig, *zero, ir, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, rp, cp), jnp.float32),
            jax.ShapeDtypeStruct((g, *lead, rp, cp), jnp.float32),
        ],
        interpret=interpret,
        name="conv_fused",
    )(patches, w, bias)

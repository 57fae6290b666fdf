"""The device image sampler: the tent contraction of
``femnist._affine_sample_jax`` against the four-tap gather form it replaced
and against the numpy host sampler, and a guard that no per-pixel gather
comes back into the compiled sampler."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import femnist

SIZE = femnist.IMAGE_SIZE


def _gather_sample(protos, classes, rots, scales, shifts):
    """The four-gather bilinear sampler, as the device sampler had it."""
    n = classes.shape[0]
    size = protos.shape[-1]
    c0 = (size - 1) / 2.0
    yy, xx = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                          jnp.arange(size, dtype=jnp.float32), indexing="ij")
    xy = jnp.stack([xx - c0, yy - c0], axis=0).reshape(2, -1)
    cos, sin = jnp.cos(rots), jnp.sin(rots)
    rot_m = jnp.stack([jnp.stack([cos, sin], -1),
                       jnp.stack([-sin, cos], -1)], -2)
    src = jnp.einsum("nij,jp->nip", rot_m, xy) * (1.0 / scales)[:, None, None]
    src = src + c0 - shifts[:, :, None]
    sx, sy = src[:, 0], src[:, 1]
    x0 = jnp.clip(jnp.floor(sx).astype(jnp.int32), 0, size - 2)
    y0 = jnp.clip(jnp.floor(sy).astype(jnp.int32), 0, size - 2)
    fx = jnp.clip(sx - x0, 0, 1)
    fy = jnp.clip(sy - y0, 0, 1)
    flat = protos[classes].reshape(n, -1)

    def tap(yv, xv):
        return jnp.take_along_axis(flat, yv * size + xv, axis=1)

    out = (tap(y0, x0) * (1 - fx) * (1 - fy) + tap(y0, x0 + 1) * fx * (1 - fy)
           + tap(y0 + 1, x0) * (1 - fx) * fy + tap(y0 + 1, x0 + 1) * fx * fy)
    oob = (sx < 0) | (sx > size - 1) | (sy < 0) | (sy > size - 1)
    return jnp.where(oob, 0.0, out).reshape(n, size, size)


def _styles(case: str, n: int, rng) -> tuple:
    """(classes, rots, scales, shifts) of one sampler case, numpy float32."""
    classes = rng.integers(0, femnist.NUM_CLASSES, n).astype(np.int32)
    zeros, ones = np.zeros(n, np.float32), np.ones(n, np.float32)
    if case in ("random", "vmapped"):
        rots = rng.normal(0.0, 0.25, n)
        scales = rng.uniform(0.8, 1.2, n)
        shifts = rng.normal(0.0, 1.5, (n, 2))
    elif case == "integer":
        rots, scales = zeros, ones
        shifts = rng.integers(-4, 5, (n, 2))
    elif case == "edges":
        # source columns and rows exactly on 0 and size - 1
        rots, scales = zeros, ones
        shifts = rng.choice([0, SIZE - 1, 1 - SIZE, 5, -5], (n, 2))
    elif case == "out_of_bounds":
        rots = rng.normal(0.0, 0.25, n)
        scales = rng.uniform(0.8, 1.2, n)
        shifts = rng.choice([-1, 1], (n, 2)) * rng.uniform(40, 60, (n, 2))
    else:
        raise ValueError(case)
    f32 = lambda a: np.asarray(a, np.float32)
    return classes, f32(rots), f32(scales), f32(shifts)


@pytest.mark.parametrize("case", ["random", "integer", "edges",
                                  "out_of_bounds", "vmapped"])
def test_tent_sampler_matches_gathers(case):
    rng = np.random.default_rng(14)
    protos = femnist.class_prototypes()
    groups, n = (3, 40) if case == "vmapped" else (1, 96)
    classes, rots, scales, shifts = _styles(case, groups * n, rng)
    tent = jax.jit(femnist._affine_sample_jax)
    if case == "vmapped":
        split = lambda a: jnp.asarray(a).reshape((groups, n) + a.shape[1:])
        batched = jax.jit(jax.vmap(femnist._affine_sample_jax,
                                   in_axes=(None, 0, 0, 0, 0)))
        out = batched(jnp.asarray(protos), split(classes), split(rots),
                      split(scales), split(shifts))
        per_group = jnp.stack([
            tent(jnp.asarray(protos), *(split(a)[g] for a in
                                        (classes, rots, scales, shifts)))
            for g in range(groups)])
        assert float(jnp.abs(out - per_group).max()) <= 1e-6
        out = np.asarray(out).reshape(groups * n, SIZE, SIZE)
    else:
        out = np.asarray(tent(jnp.asarray(protos), jnp.asarray(classes),
                              jnp.asarray(rots), jnp.asarray(scales),
                              jnp.asarray(shifts)))
    gathers = np.asarray(jax.jit(_gather_sample)(
        jnp.asarray(protos), jnp.asarray(classes), jnp.asarray(rots),
        jnp.asarray(scales), jnp.asarray(shifts)))
    host = femnist._affine_sample(protos, classes.astype(np.int64), rots,
                                  scales, shifts)
    assert out.shape == (groups * n, SIZE, SIZE) and out.dtype == np.float32
    assert np.abs(out - gathers).max() <= 1e-6
    assert np.abs(out - host).max() <= 1e-5
    if case == "out_of_bounds":
        assert not out.any()
    else:
        assert out.max() > 0.5          # the prototypes are really sampled


def test_sampler_has_no_per_pixel_gather():
    """At the paper's per-group shape the compiled generator gathers only
    whole prototype images (``protos[classes]``): a per-pixel gather runs
    about one index at a time on the chip."""
    n = 320
    lowered = jax.jit(femnist.generate_images_jax).lower(
        jax.ShapeDtypeStruct((femnist.NUM_CLASSES, SIZE, SIZE), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n, 6), jnp.float32),
        jax.random.PRNGKey(0))
    hlo = lowered.compile().as_text()
    slices = re.findall(r"= \S+ gather\(.*?slice_sizes=\{([\d,]*)\}", hlo)
    assert slices == [f"1,{SIZE},{SIZE}"], slices

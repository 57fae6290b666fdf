"""Plain reference of one FedGS deployment with the paper's CNN.

Written from the paper (Alg. 1, Alg. 2, Eqs. 4, 5, 10-17) and from the
semantics the configuration and traffic files state, in straightforward
``jax.numpy``. It imports nothing of the system under test and takes
nothing it made: the partition, the synthetic FEMNIST stream, the initial
weights, the availability, drift and corruption schedules and the test set
are all regenerated here from the seed, by the same published recipes.

What it computes for each round ``r`` of the deployment, from ``t = r*T``:
per iteration t, for every factory m,

1. the K devices' next-batch labels (Dirichlet class mix, drifted under
   ``churn``), their class counts and (when the traffic has them) the
   Markov up-mask and the quarantine bar;
2. GBP-CS (Moore-Penrose init, steepest swap until d stops falling) over
   the L - L_rnd non-pre-sampled seats, availability-aware;
3. the seated members' images (affine-jittered class prototypes);
4. Eq. 4 in gradient space: the weighted mean of the members' gradients
   (or their coordinate median, after fault injection, with the
   bounded-async stale blend, the NaN rollback and the quarantine count);
5. one SGD step; and after T iterations Eq. 5, the mean over factories,
   and the test-set loss and accuracy of the global model.

No kernels, caches or superbatch tricks: convolutions are
``lax.conv_general_dilated``, each member's gradient is its own
``value_and_grad``. Run it under ``jax.default_matmul_precision("highest")``
for float32; ``dtype=jnp.bfloat16`` computes the whole step in bfloat16
(the control), and ``half_batch=True`` trains each member on the first
half of its batch (a planted fault).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NUM_CLASSES = 62
IMAGE = 28
EPS = 1e-12


# ---------------------------------------------------------------- data ---

@functools.lru_cache(maxsize=1)
def class_prototypes() -> np.ndarray:
    """(62, 28, 28) glyph prototypes: 3-5 Gaussian blobs and 2-3 strokes
    per class, from ``default_rng(10000 + class)``, scaled to max 1."""
    size = IMAGE
    protos = np.zeros((NUM_CLASSES, size, size), np.float32)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    for c in range(NUM_CLASSES):
        rng = np.random.default_rng(10_000 + c)
        img = np.zeros((size, size), np.float32)
        for _ in range(rng.integers(3, 6)):
            cx, cy = rng.uniform(5, size - 5, 2)
            sx, sy = rng.uniform(1.2, 3.0, 2)
            img += np.exp(-(((xx - cx) / sx) ** 2 + ((yy - cy) / sy) ** 2))
        for _ in range(rng.integers(2, 4)):
            x0, y0, x1, y1 = rng.uniform(4, size - 4, 4)
            dx, dy = x1 - x0, y1 - y0
            seg2 = dx * dx + dy * dy + 1e-6
            tt = np.clip(((xx - x0) * dx + (yy - y0) * dy) / seg2, 0, 1)
            dist = np.sqrt((xx - (x0 + tt * dx)) ** 2
                           + (yy - (y0 + tt * dy)) ** 2)
            img += np.exp(-(dist / rng.uniform(0.8, 1.4)) ** 2)
        protos[c] = img / max(img.max(), 1e-6)
    return protos


def writer_styles(writer_ids: np.ndarray) -> np.ndarray:
    """(...,) writer ids -> (..., 6) persistent styles (rotation, scale,
    shift x, shift y, gain, noise) from ``default_rng(50000 + writer)``."""
    flat = np.asarray(writer_ids).reshape(-1)
    rows = []
    for w in flat:
        rng = np.random.default_rng(50_000 + int(w))
        rows.append((rng.normal(0.0, 0.18), rng.uniform(0.85, 1.15),
                     rng.normal(0.0, 1.2), rng.normal(0.0, 1.2),
                     rng.uniform(0.8, 1.2), rng.uniform(0.15, 0.3)))
    return np.asarray(rows, np.float32).reshape(np.shape(writer_ids) + (6,))


def partition(m: int, k: int, alpha: float, seed: int, bias: float = 0.5):
    """Devices as Dirichlet(alpha) writers around a per-factory prior
    (geographic tilt ``bias``); P_real is the rate-weighted mixture (Eq. 2).
    Returns (class_probs (M, K, F) f32, writer_ids (M, K), p_real (F,))."""
    f = NUM_CLASSES
    rng = np.random.default_rng(seed)
    factory_prior = rng.dirichlet(np.full(f, 1.0), size=m)
    uniform = np.full(f, 1.0 / f)
    probs = np.empty((m, k, f), np.float64)
    for mi in range(m):
        prior = (1 - bias) * uniform + bias * factory_prior[mi]
        probs[mi] = rng.dirichlet(np.maximum(prior * f * alpha, 1e-3),
                                  size=k)
    writers = rng.integers(0, 3550, size=(m, k))
    rates = np.exp(rng.normal(0.0, 0.5, size=(m, k)))
    w = rates / rates.sum()
    p_real = np.einsum("mk,mkf->f", w, probs)
    p_real = p_real / p_real.sum()
    return probs.astype(np.float32), writers, p_real.astype(np.float32)


def _bilinear(protos, classes, rots, scales, shifts):
    """Sample each class prototype under an inverse affine map (rotation,
    scale, shift about the image centre), bilinear, zero outside."""
    n = classes.shape[0]
    size = protos.shape[-1]
    c0 = (size - 1) / 2.0
    yy, xx = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                          jnp.arange(size, dtype=jnp.float32), indexing="ij")
    xy = jnp.stack([xx - c0, yy - c0], axis=0).reshape(2, -1)
    cos, sin = jnp.cos(rots), jnp.sin(rots)
    rot = jnp.stack([jnp.stack([cos, sin], -1),
                     jnp.stack([-sin, cos], -1)], -2)
    src = jnp.einsum("nij,jp->nip", rot, xy) * (1.0 / scales)[:, None, None]
    src = src + c0 - shifts[:, :, None]
    sx, sy = src[:, 0], src[:, 1]
    x0 = jnp.clip(jnp.floor(sx).astype(jnp.int32), 0, size - 2)
    y0 = jnp.clip(jnp.floor(sy).astype(jnp.int32), 0, size - 2)
    fx = jnp.clip(sx - x0, 0, 1)
    fy = jnp.clip(sy - y0, 0, 1)
    flat = protos[classes].reshape(n, -1)

    def at(yv, xv):
        return jnp.take_along_axis(flat, yv * size + xv, axis=1)

    out = (at(y0, x0) * (1 - fx) * (1 - fy) + at(y0, x0 + 1) * fx * (1 - fy)
           + at(y0 + 1, x0) * (1 - fx) * fy + at(y0 + 1, x0 + 1) * fx * fy)
    oob = (sx < 0) | (sx > size - 1) | (sy < 0) | (sy > size - 1)
    return jnp.where(oob, 0.0, out).reshape(n, size, size)


def images(protos, classes, styles, key):
    """Writer style plus per-sample jitter (rotation 0.08, scale +-5%,
    shift 0.6 px) and pixel noise, clipped to [0, 1.5]."""
    n = classes.shape[0]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    rots = styles[:, 0] + 0.08 * jax.random.normal(k1, (n,))
    scales = styles[:, 1] * jax.random.uniform(k2, (n,), minval=0.95,
                                               maxval=1.05)
    shifts = styles[:, 2:4] + 0.6 * jax.random.normal(k3, (n, 2))
    img = _bilinear(protos, classes, rots, scales, shifts)
    img = img * styles[:, 4][:, None, None]
    img = img + jax.random.normal(k4, img.shape) * styles[:, 5][:, None, None]
    return jnp.clip(img, 0.0, 1.5)


def _np_images(protos, classes, writer_ids, sample_ids):
    """Host generator of the held-out test images (numpy jitter seeded by
    the first (writer, sample) pair)."""
    n = classes.shape[0]
    styles = writer_styles(writer_ids)
    rng = np.random.default_rng(
        (int(writer_ids[0]) * 1_000_003 + int(sample_ids[0])) % (2 ** 31))
    rots = styles[:, 0] + rng.normal(0, 0.08, n).astype(np.float32)
    scales = styles[:, 1] * rng.uniform(0.95, 1.05, n).astype(np.float32)
    shifts = styles[:, 2:4] + rng.normal(0, 0.6, (n, 2)).astype(np.float32)
    size = IMAGE
    c0 = (size - 1) / 2.0
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    xy = np.stack([xx - c0, yy - c0], axis=0).reshape(2, -1)
    cos, sin = np.cos(rots), np.sin(rots)
    rot = np.stack([np.stack([cos, sin], -1), np.stack([-sin, cos], -1)], -2)
    src = np.einsum("nij,jp->nip", rot, xy) * (1.0 / scales)[:, None, None]
    src = src + c0 - shifts[:, :, None]
    sx, sy = src[:, 0], src[:, 1]
    x0 = np.clip(np.floor(sx).astype(np.int32), 0, size - 2)
    y0 = np.clip(np.floor(sy).astype(np.int32), 0, size - 2)
    fx = np.clip(sx - x0, 0, 1).astype(np.float32)
    fy = np.clip(sy - y0, 0, 1).astype(np.float32)
    flat = protos[classes.astype(np.int64)].reshape(n, -1)

    def at(yv, xv):
        return np.take_along_axis(flat, yv * size + xv, axis=1)

    out = (at(y0, x0) * (1 - fx) * (1 - fy) + at(y0, x0 + 1) * fx * (1 - fy)
           + at(y0 + 1, x0) * (1 - fx) * fy + at(y0 + 1, x0 + 1) * fx * fy)
    oob = (sx < 0) | (sx > size - 1) | (sy < 0) | (sy > size - 1)
    img = np.where(oob, 0.0, out).reshape(n, size, size).astype(np.float32)
    img = img * styles[:, 4][:, None, None]
    img = img + rng.normal(0, 1.0, img.shape).astype(np.float32) \
        * styles[:, 5][:, None, None]
    return np.clip(img, 0.0, 1.5)


def test_set(n_per_class: int = 20, seed: int = 99):
    """Balanced test set from held-out writers 900000-909999."""
    rng = np.random.default_rng(seed)
    classes = np.repeat(np.arange(NUM_CLASSES), n_per_class)
    writers = rng.integers(900_000, 910_000, size=classes.shape[0])
    samples = rng.integers(0, 2 ** 30, size=classes.shape[0])
    imgs = _np_images(class_prototypes(), classes, writers, samples)
    perm = rng.permutation(classes.shape[0])
    return imgs[perm], classes[perm].astype(np.int32)


# --------------------------------------------------------------- model ---

def init_params(key, model: dict):
    """He-style normal init (1/sqrt(fan-in)) from four splits of ``key``,
    zero biases. Layers: conv 5x5 (c1), conv 5x5 (c2), dense, dense."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c1, c2 = model["channels"]
    ks, hidden = model["kernel"], model["hidden"]
    flat = (model["image_size"] // 4) ** 2 * c2

    def he(k, shape, fan):
        return (jax.random.normal(k, shape) / jnp.sqrt(fan)).astype(
            jnp.float32)

    return {"conv1": {"w": he(k1, (ks, ks, 1, c1), ks * ks),
                      "b": jnp.zeros((c1,), jnp.float32)},
            "conv2": {"w": he(k2, (ks, ks, c1, c2), ks * ks * c1),
                      "b": jnp.zeros((c2,), jnp.float32)},
            "fc1": {"w": he(k3, (flat, hidden), flat),
                    "b": jnp.zeros((hidden,), jnp.float32)},
            "fc2": {"w": he(k4, (hidden, model["num_classes"]), hidden),
                    "b": jnp.zeros((model["num_classes"],), jnp.float32)}}


def logits(p, x):
    """[conv 5x5 SAME -> ReLU -> 2x2 max pool] x 2 -> dense ReLU -> dense."""
    h = x[..., None].astype(p["conv1"]["w"].dtype)
    for name in ("conv1", "conv2"):
        h = jax.lax.conv_general_dilated(
            h, p[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[name]["b"]
        h = jax.nn.relu(h)
        b, hh, ww, c = h.shape
        h = jnp.max(h.reshape(b, hh // 2, 2, ww // 2, 2, c), axis=(2, 4))
    h = jax.nn.relu(h.reshape(h.shape[0], -1) @ p["fc1"]["w"] + p["fc1"]["b"])
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def nll(p, x, y):
    logp = jax.nn.log_softmax(logits(p, x).astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ------------------------------------------------------------ GBP-CS ---

def _top(scores, n):
    """1 on the n largest entries (stable order), else 0."""
    order = jnp.argsort(-scores, stable=True)
    return jnp.zeros(scores.shape, jnp.float32).at[order[:n]].set(1.0)


def _dist(A, x, y):
    r = A @ x - y
    return jnp.sqrt(jnp.maximum(jnp.sum(r * r), 0.0))


def gbp_cs(A, y, l_sel, max_iters=64):
    """Alg. 2: x0 = top-L_sel of pinv(A) y; swap the x=0 entry of least
    gradient with the x=1 entry of largest gradient while d falls."""
    x0 = _top(jnp.linalg.pinv(A) @ y, l_sel)
    big = jnp.float32(jnp.finfo(jnp.float32).max)

    def cond(s):
        return (~s[2]) & (s[3] < max_iters)

    def body(s):
        x, d, _, it = s
        r = A @ x - y
        g = (A.T @ r) / jnp.sqrt(jnp.maximum(jnp.sum(r * r), 1e-12))
        one = x > 0.5
        i01 = jnp.argmin(jnp.where(one, big, g))
        i10 = jnp.argmax(jnp.where(one, g, -big))
        xn = x.at[i01].set(1.0).at[i10].set(0.0)
        dn = _dist(A, xn, y)
        better = dn < d
        return (jnp.where(better, xn, x), jnp.where(better, dn, d), ~better,
                it + 1)

    x, d, _, _ = jax.lax.while_loop(
        cond, body, (x0, _dist(A, x0, y), jnp.bool_(False), jnp.int32(0)))
    return x, d


def divergence(counts, mask, p_real):
    pooled = jnp.sum(counts * mask[:, None], axis=0)
    return jnp.linalg.norm(pooled / jnp.maximum(jnp.sum(pooled), EPS)
                           - p_real)


def select(key, counts, p_real, l, l_rnd, avail):
    """One factory's committee: L_rnd uniform pre-picks (up devices
    first), GBP-CS for the rest on y = nL P_real - b (Eq. 11), dark or
    barred devices never seated. Returns (mask (K,), divergence, d)."""
    k = counts.shape[0]
    l_sel = l - l_rnd
    if avail is not None:
        counts = counts * avail[:, None]
    key_pre, _ = jax.random.split(key)
    perm = jax.random.permutation(key_pre, k)
    if avail is not None:
        perm = perm[jnp.argsort(1.0 - avail[perm], stable=True)]
    pre, cand = perm[:l_rnd], perm[l_rnd:]
    b = jnp.sum(counts[pre], axis=0)
    A = counts[cand].T
    y = jnp.sum(counts) / k * l * p_real - b
    x, d = gbp_cs(A, y, l_sel)
    if avail is not None:
        x = _top(2.0 * avail[cand] + x, l_sel)
        d = _dist(A, x, y)
    mask = jnp.zeros((k,), jnp.float32).at[pre].set(1.0).at[cand].add(x)
    if avail is not None:
        mask = mask * avail
    return mask, divergence(counts, mask, p_real), d


# ----------------------------------------------------------- schedules ---

def _keys_of(key, ids):
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)


def churned_probs(base, t, ids, key, drift):
    """``churn``: each epoch of ``period`` iterations after the first, a
    Bernoulli(churn_rate) subset of devices streams a fresh
    Dirichlet(alpha) class mix instead of its own. ``key`` is the seed's
    root key."""
    k_ch = jax.random.fold_in(jax.random.fold_in(key, 404), 3)
    e = t // drift["period"]
    conc = jnp.full((base.shape[-1],), drift["alpha"], jnp.float32)

    def per_dev(i):
        ke = jax.random.fold_in(jax.random.fold_in(k_ch, i), e)
        hit = jax.random.bernoulli(jax.random.fold_in(ke, 1),
                                   drift["churn_rate"])
        return hit, jax.random.dirichlet(jax.random.fold_in(ke, 2), conc)

    hit, fresh = jax.vmap(per_dev)(ids)
    return jnp.where((e > 0) & hit[:, None], fresh, base)


class Markov:
    """Two-state up/down chain per device: starts Bernoulli(up_prob), then
    P(up->down) = (1-p)/dwell, P(down->up) = p/dwell per iteration; a
    device is usable when up and its U[0.5, 1.5) latency meets the
    deadline. The chain state is carried from one iteration to the next;
    ``key`` is the seed's root key."""

    def __init__(self, avail):
        self.p = avail["up_prob"]
        self.dwell = avail["dwell"]
        self.deadline = avail["deadline"]

    @staticmethod
    def _key(key, j):
        return jax.random.fold_in(jax.random.fold_in(key, 505), j)

    def first(self, key, ids):
        k_m = self._key(key, 2)
        return jax.vmap(lambda i: jax.random.bernoulli(
            jax.random.fold_in(jax.random.fold_in(k_m, i), 0),
            self.p))(ids)

    def step(self, key, state, t, ids):
        k_m = self._key(key, 2)
        u = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(k_m, i), t)))(ids)
        nxt = jnp.where(state, u >= (1.0 - self.p) / self.dwell,
                        u < self.p / self.dwell)
        return jnp.where(t > 0, nxt, state)

    def usable(self, key, state, t, ids):
        k_lat = self._key(key, 9)
        lat = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(k_lat, i), t), (),
            minval=0.5, maxval=1.5))(ids)
        return state.astype(jnp.float32) * (lat <= self.deadline)


def corrupt(grads, t, ids, key, spec):
    """Faulty devices (hashed, fraction ``frac``) fire with probability
    ``prob`` per iteration from ``t0``; each has one mode of the '+' mix:
    ``scale`` multiplies the gradient, ``nan_burst`` makes it all NaN.
    ``key`` is the seed's root key."""
    modes = spec["mode"].split("+")
    base = jax.random.fold_in(key, 606)
    k_faulty, k_mode, k_fire = (jax.random.fold_in(base, j)
                                for j in (1, 2, 3))
    faulty = jax.vmap(lambda i: jax.random.bernoulli(
        jax.random.fold_in(k_faulty, i), spec["frac"]))(ids)
    fire = jax.vmap(lambda i: jax.random.bernoulli(
        jax.random.fold_in(jax.random.fold_in(k_fire, i), t),
        spec["prob"]))(ids)
    hit = faulty & fire & (t >= spec["t0"])
    mode = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(k_mode, i), (), 0, len(modes)))(ids)

    def leaf(x):
        sh = (-1,) + (1,) * (x.ndim - 1)
        out = x
        for j, name in enumerate(modes):
            if name == "scale":
                bad = spec["scale"] * x
            elif name == "nan_burst":
                bad = jnp.full_like(x, jnp.nan)
            else:
                raise ValueError(f"corruption mode {name!r} not in the "
                                 "reference")
            out = jnp.where((hit & (mode == j)).reshape(sh), bad, out)
        return out

    return jax.tree.map(leaf, grads), hit.astype(jnp.float32)


# ------------------------------------------------------------- the run ---

class Reference:
    """The deployment of one configuration under one traffic mix, from
    ``seed``. ``run(rounds)`` returns per-round records and the global
    parameters after each round (host numpy). Everything drawn from the
    seed reaches the compiled round as an argument (``self.data``), so
    one compiled round serves every seed."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 dtype=jnp.float32, half_batch: bool = False):
        d = config["deployment"]
        self.model = config["model"]
        self.m, self.k = d["groups"], d["devices_per_group"]
        self.l, self.l_rnd = d["selected"], d["presampled"]
        self.t_round, self.lr, self.n = d["iters"], d["lr"], d["batch_size"]
        self.bounded = d["sync"] == "bounded_async"
        self.median = d["robust_agg"] == "coord_median"
        if d["robust_agg"] not in ("mean", "coord_median"):
            raise ValueError(f"robust_agg {d['robust_agg']!r} is not in the "
                             "reference")
        if d["train_step"] != "grad_avg" or d["reselect_every"] != 1:
            raise ValueError("the reference runs grad_avg with reselection "
                             "every iteration")
        self.gamma, self.max_stale = d.get("gamma"), d.get("max_staleness")
        self.clip = d.get("robust_clip", 10.0)
        self.q_limit = d.get("quarantine_limit", 0)
        self.aware = d.get("avail_selection", "aware") == "aware"
        self.dtype, self.half = dtype, half_batch
        self.drift = traffic.get("drift")
        self.avail = traffic.get("avail")
        self.fault = traffic.get("corrupt")
        if self.bounded and self.avail is None:
            raise ValueError("bounded_async needs an availability schedule")
        if self.fault is not None and not self.median:
            raise ValueError("the reference injects faults only under the "
                             "coordinate median")
        self.robust = self.median or self.fault is not None
        self.quarantine = self.fault is not None and self.q_limit > 0

        probs, writers, p_real = partition(self.m, self.k, traffic["alpha"],
                                           seed)
        self.data = {
            "probs": jnp.asarray(probs.reshape(-1, NUM_CLASSES)),
            "styles": jnp.asarray(writer_styles(writers).reshape(-1, 6)),
            "p_real": jnp.asarray(p_real),
            "key": jax.random.PRNGKey(seed)}
        self.protos = jnp.asarray(class_prototypes())
        tx, ty = test_set()
        self.test_x, self.test_y = jnp.asarray(tx), jnp.asarray(ty)
        self.markov = Markov(self.avail) if self.avail else None
        self.params0 = init_params(jax.random.PRNGKey(seed), self.model)

    # -- one factory's stream at iteration t --
    def labels(self, d, t, gid):
        ids = gid * self.k + jnp.arange(self.k, dtype=jnp.int32)
        p = d["probs"][ids]
        if self.drift is not None:
            p = churned_probs(p, t, ids, d["key"], self.drift)
        kg = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(d["key"], 101), t), gid)
        u = jax.random.uniform(kg, (self.k, self.n, 1))
        lab = jnp.sum(u > jnp.cumsum(p, axis=-1)[:, None, :], axis=-1)
        return jnp.minimum(lab, NUM_CLASSES - 1).astype(jnp.int32)

    def batch(self, d, t, gid, labels, idx):
        ids = gid * self.k + jnp.arange(self.k, dtype=jnp.int32)
        sty = jnp.repeat(d["styles"][ids][idx], self.n, axis=0)
        kg = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(d["key"], 202), t), gid)
        imgs = images(self.protos, labels[idx].reshape(-1), sty, kg)
        return imgs.reshape(self.l, self.n, IMAGE, IMAGE), labels[idx]

    def member_losses_grads(self, p, x, y):
        if self.half:
            x, y = x[:, : self.n // 2], y[:, : self.n // 2]
        x = x.astype(self.dtype)
        return jax.vmap(lambda xb, yb: jax.value_and_grad(nll)(p, xb, yb))(
            x, y)

    def iteration(self, d, carry, t):
        gp, key, chain, stale, g_prev, quar = carry
        m, k, l = self.m, self.k, self.l
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, m)
        gids = jnp.arange(m, dtype=jnp.int32)
        labels = jax.vmap(lambda g: self.labels(d, t, g))(gids)   # (M,K,n)
        counts = jnp.sum(labels[..., None] == jnp.arange(NUM_CLASSES),
                         axis=2).astype(jnp.float32)              # (M,K,F)
        flat_ids = (gids[:, None] * k + jnp.arange(k)).reshape(-1)
        avail = None
        if self.markov is not None:
            chain = self.markov.step(d["key"], chain, t, flat_ids)
            avail = self.markov.usable(d["key"], chain, t,
                                       flat_ids).reshape(m, k)
        sel_avail = avail if self.aware else None
        if self.quarantine:
            ok = (quar < self.q_limit).astype(jnp.float32)
            sel_avail = ok if sel_avail is None else sel_avail * ok
        if sel_avail is None:
            mask, div, dist = jax.vmap(
                lambda kk, c: select(kk, c, d["p_real"], l, self.l_rnd,
                                     None))(keys, counts)
        else:
            mask, div, dist = jax.vmap(
                lambda kk, c, a: select(kk, c, d["p_real"], l, self.l_rnd,
                                        a))(keys, counts, sel_avail)
        vals, idx = jax.lax.top_k(mask, l)                        # (M, L)
        xs, ys = jax.vmap(lambda g, lab, i: self.batch(d, t, g, lab, i))(
            gids, labels, idx)
        fresh_w = vals if avail is None else \
            vals * jnp.take_along_axis(avail, idx, axis=-1)
        extra = {}
        if self.bounded:
            stale_m = mask * (1.0 - avail)
            stale_sum = jnp.sum(stale_m * self.gamma ** jnp.maximum(
                stale.astype(jnp.float32), 0.0), axis=-1)
            s_f = stale.astype(jnp.float32)
            n_stale = jnp.sum(stale_m, axis=-1)
            extra["staleness_mean"] = jnp.mean(
                jnp.sum(stale_m * s_f, -1) / jnp.maximum(n_stale, 1.0))
            extra["staleness_max"] = jnp.max(stale_m * s_f)
            extra["dark_selected"] = jnp.sum(n_stale)
            stale_new = jnp.where(mask * avail > 0, 0,
                                  jnp.minimum(stale + 1, self.max_stale))
        else:
            stale_sum = jnp.zeros((m,), jnp.float32)
            stale_new = stale
            if avail is not None:
                extra["dark_selected"] = jnp.sum(mask * (1.0 - avail))
        if avail is not None:
            extra["participation"] = jnp.mean(avail)

        member_ids = jnp.take_along_axis(
            gids[:, None] * k + jnp.arange(k), idx, axis=-1)

        def group_step(p, x, y, w, ss, gpv, ids):
            losses, grads = self.member_losses_grads(p, x, y)
            grads = jax.tree.map(lambda a: a.astype(jnp.float32), grads)
            hit = jnp.zeros((l,), jnp.float32)
            if self.fault is not None:
                grads, hit = corrupt(grads, t, ids, d["key"], self.fault)
            finite = jnp.ones((l,), bool)
            sq = jnp.zeros((l,), jnp.float32)
            for a in jax.tree.leaves(grads):
                a2 = a.reshape(l, -1)
                finite = finite & jnp.all(jnp.isfinite(a2), axis=1)
                a2 = jnp.where(jnp.isfinite(a2), a2, 0.0)
                sq = sq + jnp.sum(a2 * a2, axis=1)
            flags = (~finite | (jnp.sqrt(sq) > self.clip)).astype(
                jnp.float32)
            if self.median:
                act = (w > 0) & finite
                cnt = jnp.sum(act.astype(jnp.int32))
                lo = jnp.maximum((cnt - 1) // 2, 0)
                hi = jnp.minimum(cnt // 2, l - 1)
                big = jnp.float32(jnp.finfo(jnp.float32).max)

                def med(a):
                    v = jnp.where(act.reshape((-1,) + (1,) * (a.ndim - 1)),
                                  a, big)
                    s = jnp.sort(v, axis=0)
                    out = (jnp.take(s, lo, axis=0)
                           + jnp.take(s, hi, axis=0)) * 0.5
                    return jnp.where(cnt > 0, out, 0.0)

                g = jax.tree.map(med, grads)
                w_fresh = jnp.sum(w * finite)
            else:
                wn = w / jnp.maximum(jnp.sum(w) + ss, EPS)
                g = jax.tree.map(
                    lambda a: jnp.tensordot(wn, a, axes=1), grads)
                w_fresh = None
            if self.bounded:
                if w_fresh is None:
                    frac = ss / jnp.maximum(jnp.sum(w) + ss, EPS)
                    g = jax.tree.map(lambda a, b: a + frac * b, g, gpv)
                else:
                    den = jnp.maximum(w_fresh + ss, EPS)
                    g = jax.tree.map(lambda a, b: (w_fresh * a + ss * b)
                                     / den, g, gpv)
            newp = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - self.lr * b).astype(
                    a.dtype), p, g)
            return newp, jnp.mean(losses), g, hit, flags

        if self.bounded:
            g_in = g_prev
        else:
            g_in = jax.tree.map(jnp.zeros_like, gp)
        new_gp, losses, g_out, hit, flags = jax.vmap(group_step)(
            gp, xs, ys, fresh_w, stale_sum, g_in, member_ids)
        if self.robust:
            extra["corrupted_selected"] = jnp.sum(hit * vals)
            extra["clipped_fraction"] = jnp.sum(flags * vals) / jnp.maximum(
                jnp.sum(vals), 1.0)
        if self.fault is not None:
            ok = jnp.ones((m,), bool)
            trees = (new_gp, g_out) if self.bounded else (new_gp,)
            for a in jax.tree.leaves(trees):
                ok = ok & jnp.all(jnp.isfinite(a.reshape(m, -1)), axis=1)

            def keep(n, o):
                return jnp.where(ok.reshape((-1,) + (1,) * (n.ndim - 1)),
                                 n, o)

            new_gp = jax.tree.map(keep, new_gp, gp)
            if self.bounded:
                g_out = jax.tree.map(keep, g_out, g_prev)
                stale_new = jnp.where(ok[:, None], stale_new, stale)
            extra["rollbacks"] = jnp.sum(1.0 - ok.astype(jnp.float32))
        if self.quarantine:
            quar = jax.vmap(lambda q, i, f: q.at[i].add(f.astype(jnp.int32)))(
                quar, idx, flags * vals)
        if self.bounded:
            g_prev = g_out
        disc = jnp.linalg.norm(
            jnp.sum(counts, axis=1) / jnp.maximum(
                jnp.sum(counts, axis=(1, 2))[:, None], EPS) - d["p_real"],
            axis=-1)
        rec = {"loss": jnp.mean(losses), "divergence": jnp.mean(div),
               "group_discrepancy": jnp.mean(disc),
               "selection_distance": jnp.mean(dist), **extra}
        return (new_gp, key, chain, stale_new, g_prev, quar), rec

    def _round(self, d, carry, r):
        t0 = r * self.t_round
        carry, recs = jax.lax.scan(
            functools.partial(self.iteration, d), carry,
            t0 + jnp.arange(self.t_round, dtype=jnp.int32))
        gp = carry[0]
        glob = jax.tree.map(lambda a: jnp.mean(a.astype(jnp.float32), axis=0)
                            .astype(a.dtype), gp)
        gp = jax.tree.map(lambda a: jnp.broadcast_to(a, (self.m,) + a.shape),
                          glob)
        out = {}
        for name, v in recs.items():
            if name in ("dark_selected", "corrupted_selected", "rollbacks"):
                out[name] = jnp.sum(v)
            elif name == "staleness_max":
                out[name] = jnp.max(v)
            else:
                out[name] = jnp.mean(v)
        lg = logits(glob, self.test_x.astype(self.dtype)).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, -1)
        out["test_loss"] = -jnp.mean(
            jnp.take_along_axis(logp, self.test_y[:, None], -1))
        out["test_accuracy"] = jnp.mean(
            (jnp.argmax(lg, -1) == self.test_y).astype(jnp.float32))
        return (gp,) + carry[1:], out, glob

    def initial_carry(self):
        """Global model per factory, key, availability chain, staleness,
        last aggregates and quarantine counts before round 0."""
        m, k = self.m, self.k
        gp = jax.tree.map(
            lambda a: jnp.broadcast_to(a.astype(self.dtype), (m,) + a.shape),
            self.params0)
        flat_ids = jnp.arange(m * k, dtype=jnp.int32)
        chain = (self.markov.first(self.data["key"], flat_ids) if self.markov
                 else jnp.zeros((m * k,), bool))
        stale = jnp.full((m, k), self.max_stale or 0, jnp.int32)
        g_prev = jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32), gp)
        quar = jnp.zeros((m, k), jnp.int32)
        return (gp, self.data["key"], chain, stale, g_prev, quar)

    def run(self, rounds: int):
        carry = self.initial_carry()
        step = jax.jit(self._round)
        records, params = [], []
        for r in range(rounds):
            carry, rec, glob = step(self.data, carry, jnp.int32(r))
            records.append({n: float(v) for n, v in rec.items()})
            params.append(jax.tree.map(
                lambda a: np.asarray(a.astype(jnp.float32)), glob))
        return records, params

    def initial_params(self):
        return jax.tree.map(np.asarray, self.params0)

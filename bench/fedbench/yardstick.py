"""Peaks of the chips and the work a FedGS round requires, counted from
shapes. Nothing here reads the program."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM per chip. Keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _layers(model: dict) -> list[tuple[str, int, int, int]]:
    """(kind, output positions, contraction, outputs) per layer of the
    paper's CNN: two SAME 5x5 convolutions, each before a 2x2 pool, then
    two dense layers."""
    s, k = model["image_size"], model["kernel"]
    c1, c2 = model["channels"]
    flat = (s // 4) ** 2 * c2
    return [("conv", s * s, k * k, c1),
            ("conv", (s // 2) ** 2, k * k * c1, c2),
            ("dense", 1, flat, model["hidden"]),
            ("dense", 1, model["hidden"], model["num_classes"])]


def forward_flops_per_sample(model: dict) -> int:
    return sum(2 * pos * q * c for _, pos, q, c in _layers(model))


def train_flops_per_sample(model: dict) -> int:
    """Forward, weight gradients (as many FLOP as the forward) and input
    gradients of every layer but the first, whose input is the image.
    Nothing recomputed, no eval, selection or aggregation."""
    per = [2 * pos * q * c for _, pos, q, c in _layers(model)]
    return 2 * sum(per) + sum(per[1:])


def param_count(model: dict) -> int:
    return sum(q * c + c for _, _, q, c in _layers(model))


def samples_per_round(deployment: dict) -> int:
    """M·L·n·T: the images the seated committees train on in one round."""
    d = deployment
    return d["groups"] * d["selected"] * d["batch_size"] * d["iters"]


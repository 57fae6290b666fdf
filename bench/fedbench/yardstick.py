"""Peaks of the chips and the work a FedGS round requires. What a sample
of one model costs is counted by that configuration's counts module
(``bench/configs/<counts>.py``, named by the configuration's ``counts``
key). Nothing here reads the program."""
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


def samples_per_round(deployment: dict) -> int:
    """M·L·n·T: the samples the seated committees train on in one round."""
    d = deployment
    return d["groups"] * d["selected"] * d["batch_size"] * d["iters"]


def mfu_percent(counts, config: dict, traffic: dict, samples_per_s: float,
                chips: int, flops_per_s: float) -> float:
    """Training FLOP per sample (the configuration's ``counts``) times
    samples per second, over the chips' peak, in %."""
    return (100.0 * counts.train_flops_per_sample(config, traffic)
            * samples_per_s / (chips * flops_per_s))

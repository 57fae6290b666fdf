"""Work counts of the paper's FEMNIST CNN, counted from the configuration's
shapes: parameters and training FLOP per sample, and the toy preset the CPU
tests run its cells with. Nothing here reads the program."""
from __future__ import annotations

# The smoke CNN (``launch/train.py --smoke-model``): the same layers at
# toy widths.
SMOKE = {"model": {"image_size": 28, "channels": [8, 16], "kernel": 5,
                   "hidden": 128, "num_classes": 62},
         "flags": ["--smoke-model"]}


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


def forward_flops_per_sample(config: dict) -> int:
    return sum(2 * pos * q * c for _, pos, q, c in _layers(config["model"]))


def train_flops_per_sample(config: dict, traffic: dict) -> int:
    """Forward, weight gradients (as many FLOP as the forward) and input
    gradients of every layer but the first, whose input is the image.
    Nothing recomputed, no eval, selection or aggregation. An image is an
    image whatever the traffic, which is not read."""
    per = [2 * pos * q * c for _, pos, q, c in _layers(config["model"])]
    return 2 * sum(per) + sum(per[1:])


def param_count(config: dict) -> int:
    return sum(q * c + c for _, _, q, c in _layers(config["model"]))

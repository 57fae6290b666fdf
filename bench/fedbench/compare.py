"""The numbers that decide ``correct``: what the timed path produced in its
first rounds against what the plain reference produced from the same seed.

Norms are compared leaf by leaf, as the gap between the two norms (not the
norm of the difference) over the larger of the reference leaf's norm and
the median leaf's, worst leaf reported. Leaves whose reference update is
under a thousandth of the median leaf's are left out: their movement is
round-off on both sides.
"""
from __future__ import annotations

import math

import numpy as np

# per-round records compared as relative gaps, worst round reported
RECORD_FIELDS = ("loss", "test_loss", "group_discrepancy",
                 "selection_distance", "participation", "clipped_fraction")


def _leaves(tree, prefix: str = "") -> list[tuple[str, np.ndarray]]:
    out = []
    for key in sorted(tree):
        v = tree[key]
        out += _leaves(v, f"{prefix}{key}.") if isinstance(v, dict) else [
            (prefix + key, np.asarray(v, np.float64))]
    return out


def _norms(a, b) -> np.ndarray:
    return np.array([float(np.linalg.norm(x - y))
                     for (_, x), (_, y) in zip(_leaves(a), _leaves(b))])


def leaf_norms(params, p0) -> dict:
    """Per-leaf norm of ``params - p0`` (for reports)."""
    return {name: float(np.linalg.norm(x - y))
            for (name, x), (_, y) in zip(_leaves(params), _leaves(p0))}


def norm_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    med = float(np.median(ref))
    keep = ref >= 1e-3 * med
    scale = np.maximum(ref, med)
    gaps = np.abs(prog - ref) / scale
    return float(np.max(gaps[keep])) if keep.any() else math.nan


def readings(prog_p0, prog_params: list, prog_recs: list[dict],
             ref_p0, ref_params: list, ref_recs: list[dict]) -> dict:
    """``*_params`` are the global parameters after each compared round,
    ``*_recs`` each round's record (field -> value)."""
    out = {
        "update1": norm_gap(_norms(prog_params[0], prog_p0),
                            _norms(ref_params[0], ref_p0)),
        "change": norm_gap(_norms(prog_params[-1], prog_p0),
                           _norms(ref_params[-1], ref_p0)),
    }
    for field in RECORD_FIELDS:
        if field not in ref_recs[0]:
            continue
        gaps = []
        for p, r in zip(prog_recs, ref_recs):
            pv, rv = p.get(field), r[field]
            pv = math.nan if pv is None else pv
            gaps.append(abs(pv - rv) / max(abs(rv), 1e-12))
        out[field] = max(gaps)
    return out


def check(values: dict, limits: dict) -> dict:
    """name -> {"value", "limit"} for every number with a limit. A missing
    or non-finite value fails its limit."""
    return {name: {"value": values.get(name, math.nan), "limit": lim}
            for name, lim in limits.items()}


def passed(checks: dict) -> bool:
    return all(isinstance(c["value"], float) and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())

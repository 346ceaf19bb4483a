"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the reference computes, each held to its own limit."""

from __future__ import annotations

import math

import numpy as np


def rel_max_gap(got, ref, mask=None, floor: float = 0.0) -> float:
    """max |got - ref| over the mask, in units of max(max |ref| there,
    floor)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if mask is not None:
        got, ref = got[..., mask], ref[..., mask]
    scale = max(float(np.max(np.abs(ref), initial=0.0)), floor)
    gap = float(np.max(np.abs(got - ref), initial=0.0))
    if not math.isfinite(gap):
        return math.inf
    return gap / scale if scale > 0 else gap


def pair_gap(got, ref, floor: float) -> float:
    """Largest gap of the stopping pair (violation, duality gap), each in
    units of max(|reference value|, floor)."""
    out = 0.0
    for g, r in zip(got, ref):
        g, r = float(g), float(r)
        if not math.isfinite(g):
            return math.inf
        out = max(out, abs(g - r) / max(abs(r), floor))
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and at least one number compared."""
    checks = {}
    ok = bool(values)
    for name, value in values.items():
        limit = limits.get(name)
        value = float(value)
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return ok, checks

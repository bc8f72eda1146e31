"""The comparison that decides ``correct``.

Each compared number is a relative error: max |program - reference| over
max |reference|, in float64, over every answer compared. A non-finite
answer, or one that never came, reads as infinity. Each number has a limit
of its own, stated in the cell's workload file (``limits``) with the
readings it was set from in ``PERF.md``; a run is correct when every number
is at or under its limit and nothing failed.
"""
from __future__ import annotations

import math

import numpy as np


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or got.size == 0:
        return math.inf
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return math.inf
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(got - ref))) / scale


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(all within limits, {name: {"value", "limit"}}) in the order of
    ``limits``; a number without a reading fails."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name, math.inf)
        table[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, table

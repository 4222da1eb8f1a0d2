"""The comparisons that decide `correct`, shared by every driver.

Each number compared goes out as `{"value": v, "limit": l}`, the limit
taken from the traffic file. Nothing here imports the system under test.
"""
from __future__ import annotations

import numpy as np


def limits(traffic: dict, values: dict) -> dict:
    """{name: {"value", "limit"}} for every number compared."""
    return {k: {"value": v, "limit": traffic["limits"][k]}
            for k, v in values.items()}


def degrees(num_vertices: int, src, dst):
    """[V] degree of every vertex in the undirected generated edge list
    (each stored direction counts once, so out-degree == degree)."""
    return np.bincount(src, minlength=num_vertices) + \
        np.bincount(dst, minlength=num_vertices)


def sssp_errors(got, want):
    """(largest relative error, vertices whose reachability differs) of
    one distance vector against the reference's. Unreachable is inf on
    both sides; where the reference distance is 0 the error is absolute."""
    got = np.asarray(got, np.float64)
    fin = np.isfinite(want)
    both = fin & np.isfinite(got)
    scale = np.where(want[both] > 0, want[both], 1.0)
    err = np.abs(got[both] - want[both]) / scale
    return float(err.max(initial=0.0)), int((np.isfinite(got) != fin).sum())

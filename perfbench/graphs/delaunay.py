"""Delaunay triangulations of uniform random points in the unit square.

The DIMACS10 ``delaunay_n<k>`` graphs were made this way: ``2**k``
points drawn uniformly at random in the unit square, triangulated, and
every triangle side taken as an undirected edge. Here the points come
from ``params["seed"]`` and ``scipy.spatial.Delaunay`` triangulates
them; the edges are listed in ascending ``(min, max)`` vertex order.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict):
    from scipy.spatial import Delaunay

    n = int(params["n"])
    rng = np.random.default_rng(int(params["seed"]))
    points = rng.random((n, 2))
    tri = Delaunay(points).simplices
    sides = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    lo = sides.min(axis=1).astype(np.int64)
    hi = sides.max(axis=1).astype(np.int64)
    key = np.unique(lo * n + hi)
    return (key // n).astype(np.int32), (key % n).astype(np.int32)

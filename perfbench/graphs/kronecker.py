"""RMAT / Kronecker graphs with the Graph500 initiator.

A copy of the repository's ``repro.graph.generators.kronecker_graph``
(kept here so that no change to the program can change the benchmark's
inputs): ``edge_factor * 2**scale`` draws of one quadrant per bit from
the initiator ``(a, b, c, 1-a-b-c)``, self-loops dropped, duplicate
unordered pairs dropped, first occurrence kept in draw order.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict):
    scale = int(params["scale"])
    a, b, c, d = (float(x) for x in params["initiator"])
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"initiator {params['initiator']} does not sum to 1")
    n = 1 << scale
    if int(params["n"]) != n:
        raise ValueError(f"n {params['n']} != 2**scale {n}")
    m = int(params["edge_factor"]) * n
    rng = np.random.default_rng(int(params["seed"]))
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        r = rng.random(m)
        go_right = r > ab
        r2 = rng.random(m)
        thresh = np.where(go_right, c / (c + (1 - abc)), a / ab)
        go_down = r2 > thresh
        src |= go_right.astype(np.int64) << bit
        dst |= go_down.astype(np.int64) << bit
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    _, first = np.unique(key, return_index=True)
    first.sort()
    return src[first].astype(np.int32), dst[first].astype(np.int32)

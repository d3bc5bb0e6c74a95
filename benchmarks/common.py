"""Benchmark utilities. Every benchmark returns rows
(name, us_per_call, derived) and benchmarks/run.py prints them as CSV.

Wall-times taken on the CPU (interpret-mode Pallas) are *sanity
numbers*, never device speeds. Sizes are scaled down from the
paper's 2^16..2^21 Kronecker graphs to keep the suite minutes-long on one
CPU core; the scaling *trends* (the figures' shapes) are what is checked.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import EdgeStream, SubstreamConfig
from repro.graph.generators import kronecker_graph, uniform_weights


def timed(fn, *args, reps: int = 3, warmup: int = 1, **kw):
    """Best-of-``reps`` wall time of ``fn``; every call is waited on with
    ``jax.block_until_ready``, so a device error raises here instead of
    being timed as a fast call."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def make_workload(scale: int, edge_factor: int, L: int, eps: float, seed: int = 0):
    src, dst = kronecker_graph(scale, edge_factor, seed=seed)
    w = uniform_weights(len(src), L, eps, seed=seed)
    n = 1 << scale
    cfg = SubstreamConfig(n=n, L=L, eps=eps)
    stream = EdgeStream.from_numpy(src, dst, w)
    return stream, cfg

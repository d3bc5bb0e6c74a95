"""``merge_host`` decides one substream group at a time.

The edges recorded under one substream are vertex-disjoint, so the greedy
merge can decide a whole group with one gather, mask and scatter. These
tests hold it to the sequential per-edge greedy loop of Listing 1 Part 2
(kept here, independent of the library) on clean Part-1 results, and on
corrupted ones, where a group that is not disjoint falls back to the loop.
"""
import numpy as np
import pytest

from repro import obs
from repro.core import EdgeStream, MatchingResult, SubstreamConfig, merge_host, mwm_scan
from repro.graph.generators import kronecker_graph, uniform_weights
from repro.testing import faultline


def _loop_merge(stream, assigned, cfg):
    """Listing 1 Part 2 as written: descending substream, then stream
    position, one edge at a time."""
    src, dst = np.asarray(stream.src), np.asarray(stream.dst)
    assigned = np.asarray(assigned)
    recorded = np.nonzero(assigned >= 0)[0]
    order = recorded[np.argsort(cfg.L - 1 - assigned[recorded].astype(np.int64), kind="stable")]
    taken = np.zeros(cfg.n, dtype=bool)
    out = []
    for e in order.tolist():
        u, v = src[e], dst[e]
        if not taken[u] and not taken[v]:
            taken[u] = taken[v] = True
            out.append(e)
    return np.sort(np.asarray(out, dtype=np.int64))


def _merge_counted(stream, res, cfg):
    tel = obs.Telemetry()
    merged = merge_host(stream, res, cfg, telemetry=tel)
    return merged, tel.counters


def _random(n, m, L, seed, pad=0):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    stream = EdgeStream.from_numpy(src, dst, uniform_weights(m, L, 0.1, seed), n_pad=m + pad)
    return stream, SubstreamConfig(n=n, L=L)


def _kronecker(scale, L, seed, pad=0):
    src, dst = kronecker_graph(scale, edge_factor=8, seed=seed)
    m = src.size
    stream = EdgeStream.from_numpy(src, dst, uniform_weights(m, L, 0.1, seed), n_pad=m + pad)
    return stream, SubstreamConfig(n=1 << scale, L=L)


def _dropped(n, m, L, seed):
    # every weight below (1+eps)^0 = 1: no substream admits an edge
    rng = np.random.default_rng(seed)
    stream = EdgeStream.from_numpy(
        rng.integers(0, n, m), rng.integers(0, n, m), np.full(m, 0.5), n_pad=m + 3
    )
    return stream, SubstreamConfig(n=n, L=L)


def _empty(L):
    e = np.zeros(0, np.int64)
    return EdgeStream.from_numpy(e, e, np.zeros(0)), SubstreamConfig(n=4, L=L)


CASES = {
    "random_L1": lambda: _random(64, 400, 1, 0),
    "random_L8": lambda: _random(64, 400, 8, 1),
    "random_L13_padded": lambda: _random(96, 700, 13, 2, pad=9),
    "random_L64": lambda: _random(128, 1500, 64, 3),
    "random_L64_padded": lambda: _random(128, 1500, 64, 4, pad=17),
    "kronecker_L1": lambda: _kronecker(8, 1, 5),
    "kronecker_L8_padded": lambda: _kronecker(8, 8, 6, pad=5),
    "kronecker_L13": lambda: _kronecker(8, 13, 7),
    "kronecker_L64": lambda: _kronecker(9, 64, 8),
    "kronecker_L64_padded": lambda: _kronecker(9, 64, 9, pad=11),
    "all_dropped_L8": lambda: _dropped(32, 200, 8, 10),
    "empty_L13": lambda: _empty(13),
}


@pytest.mark.parametrize("layout", ["packed", "dense"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_merge_equals_the_per_edge_loop(case, layout):
    stream, cfg = CASES[case]()
    res = mwm_scan(stream, cfg)
    if layout == "packed":
        res = MatchingResult(assigned=res.assigned, mb_packed=res.packed(), L=cfg.L)
    else:
        res = MatchingResult(assigned=res.assigned, mb=res.mb)
    merged, counters = _merge_counted(stream, res, cfg)
    want = _loop_merge(stream, res.assigned, cfg)
    assert merged.dtype == np.int64
    np.testing.assert_array_equal(merged, want)
    assigned = np.asarray(res.assigned)
    recorded = assigned[assigned >= 0]
    assert counters.get("merge.greedy_groups") == np.unique(recorded).size
    assert counters.get("merge.recorded_edges") == recorded.size
    assert counters.get("merge.matched_edges") == want.size
    assert counters.get("merge.loop_edges") == 0


def _first_recorded(res):
    return int(np.flatnonzero(np.asarray(res.assigned) >= 0)[0])


def _duplicate_substream():
    # equal-weight star: one hub edge is recorded; a second hub edge
    # corrupted into the same substream shares the hub with it
    edges = [(0, i, 5.0) for i in range(1, 9)]
    src, dst, w = (np.asarray(x) for x in zip(*edges))
    stream = EdgeStream.from_numpy(src, dst, w)
    cfg = SubstreamConfig(n=9, L=8)
    res = mwm_scan(stream, cfg)
    p = _first_recorded(res)
    other = 1 if p != 1 else 2
    bad = faultline.corrupt_assigned(res, other, int(np.asarray(res.assigned)[p]))
    return stream, cfg, res, bad


def _self_loop():
    # a self-loop on a vertex no other edge touches, recorded under
    # substream 0: its group holds a self-loop, so it is not decided at once
    stream, cfg = _random(32, 120, 12, 6)
    src = np.asarray(stream.src).copy()
    dst = np.asarray(stream.dst).copy()
    src[0] = dst[0] = cfg.n - 1
    src[1:][src[1:] == cfg.n - 1] = 0
    dst[1:][dst[1:] == cfg.n - 1] = 0
    loop_stream = EdgeStream(src=src, dst=dst, weight=stream.weight, valid=stream.valid)
    res = mwm_scan(loop_stream, cfg)
    return loop_stream, cfg, res, faultline.corrupt_assigned(res, 0, 0)


def _out_of_range():
    # a substream beyond L-1 keys below every clean one in the merge order
    stream, cfg = _random(48, 300, 8, 7)
    res = mwm_scan(stream, cfg)
    p = int(np.flatnonzero(np.asarray(res.assigned) < 0)[0])
    return stream, cfg, res, faultline.corrupt_assigned(res, p, cfg.L + 3)


def _unrecorded_into_substream_0():
    # every unrecorded edge rewritten into substream 0: a group of
    # hundreds of edges that share vertices, decided in stream order
    stream, cfg = _random(48, 400, 1, 8)
    res = mwm_scan(stream, cfg)
    assigned = np.asarray(res.assigned)
    return stream, cfg, res, res.with_assigned(np.where(assigned >= 0, assigned, 0))


FAULTS = {
    "duplicate_substream": (_duplicate_substream, True),
    "unrecorded_into_substream_0": (_unrecorded_into_substream_0, True),
    "self_loop": (_self_loop, True),
    "out_of_range": (_out_of_range, False),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_corrupted_result_merges_like_the_per_edge_loop(fault):
    make, falls_back = FAULTS[fault]
    stream, cfg, clean, bad = make()
    merged, counters = _merge_counted(stream, clean, cfg)
    np.testing.assert_array_equal(merged, _loop_merge(stream, clean.assigned, cfg))
    assert counters.get("merge.loop_edges") == 0
    merged, counters = _merge_counted(stream, bad, cfg)
    np.testing.assert_array_equal(merged, _loop_merge(stream, bad.assigned, cfg))
    assert (counters.get("merge.loop_edges") > 0) == falls_back

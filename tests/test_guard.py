"""Adversarial-input zoo for the guard layer (`repro.core.guard`).

Dirty streams — NaN/Inf/negative weights, ids ≥ n, the sacrificial-row
(``n_pad``) collision, duplicate and self-loop floods, empty streams —
are sanitized and then run through **every** Part-1 engine, which must
agree bit-for-bit with the scan baseline on the repaired stream (and
with a manually cleaned stream). Also pins the `from_numpy` cast guards
(satellite: no more silent int64 wrap / NaN propagation), the
m == 0 / all-dropped / n == 0 degenerate paths, and the shape of
`ValidationReport` counters the bench embeds.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EdgeStream,
    StreamValidationError,
    SubstreamConfig,
    check_matching,
    exact_mwm_weight,
    matching_weight,
    merge_device,
    merge_host,
    mwm_scan,
    mwm_waves,
    validate_stream,
)
from repro.core.guard import stream_problems
from repro.kernels.substream_match.ops import substream_match
from repro.kernels.substream_match.ref import substream_match_ref
from repro.testing.faultline import sacrificial_row


def _dirty(n, src, dst, w, L=12, pad=0):
    """Build a stream letting dirt through (policy='off'), plus its cfg."""
    stream = EdgeStream.from_numpy(
        np.asarray(src), np.asarray(dst), np.asarray(w),
        n_pad=len(src) + pad, policy="off",
    )
    return stream, SubstreamConfig(n=n, L=L)


def _zoo_nan_weights():
    rng = np.random.default_rng(21)
    w = rng.uniform(0.5, 6.0, 60)
    w[::7] = np.nan
    return _dirty(24, rng.integers(0, 24, 60), rng.integers(0, 24, 60), w)


def _zoo_inf_weights():
    rng = np.random.default_rng(22)
    w = rng.uniform(0.5, 6.0, 60)
    w[3] = np.inf
    w[10] = -np.inf
    return _dirty(24, rng.integers(0, 24, 60), rng.integers(0, 24, 60), w)


def _zoo_negative_weights():
    rng = np.random.default_rng(23)
    w = rng.uniform(0.5, 6.0, 60)
    w[5::11] = -2.25
    return _dirty(24, rng.integers(0, 24, 60), rng.integers(0, 24, 60), w)


def _zoo_ids_past_n():
    rng = np.random.default_rng(24)
    src = rng.integers(0, 24, 60)
    dst = rng.integers(0, 24, 60)
    src[4] = 24          # == n: the first silently-clamped row
    dst[9] = 1_000_000   # far out of range
    src[17] = -3
    return _dirty(24, src, dst, rng.uniform(0.5, 6.0, 60))


def _zoo_sacrificial_collision():
    # ids at n_pad — the padding row the row-addressed kernels scatter
    # padding slots to; a colliding real edge would alias it
    n = 21  # n_pad = 24 > n, so the collision row exists
    rng = np.random.default_rng(25)
    src = rng.integers(0, n, 60)
    dst = rng.integers(0, n, 60)
    dst[[2, 30]] = sacrificial_row(n)
    return _dirty(n, src, dst, rng.uniform(0.5, 6.0, 60))


def _zoo_dup_self_loop_flood():
    # degenerate but *legal* dirt: sanitize must drop nothing
    edges = [(3, 3, 9.0)] * 10 + [(1, 4, 5.0)] * 8 + [(4, 1, 5.0)] * 5
    src, dst, w = (np.asarray(x) for x in zip(*edges))
    return _dirty(8, src, dst, w, pad=3)


def _zoo_everything_at_once():
    rng = np.random.default_rng(26)
    src = rng.integers(0, 24, 80)
    dst = rng.integers(0, 24, 80)
    w = rng.uniform(0.5, 6.0, 80)
    src[0] = -1
    dst[1] = 99
    w[2] = np.nan
    w[3] = np.inf
    w[4] = -0.5
    src[5] = dst[5] = 7  # legal self-loop stays
    return _dirty(24, src, dst, w, pad=5)


def _zoo_empty():
    return _dirty(8, [], [], [])


DIRTY_ZOO = {
    "nan_weights": _zoo_nan_weights,
    "inf_weights": _zoo_inf_weights,
    "negative_weights": _zoo_negative_weights,
    "ids_past_n": _zoo_ids_past_n,
    "sacrificial_collision": _zoo_sacrificial_collision,
    "dup_self_loop_flood": _zoo_dup_self_loop_flood,
    "everything_at_once": _zoo_everything_at_once,
    "empty": _zoo_empty,
}

#: graphs where sanitize legitimately drops nothing
CLEAN_DIRT = {"dup_self_loop_flood", "empty"}


def _run_scan(stream, cfg):
    r = mwm_scan(stream, cfg)
    return np.asarray(r.assigned), np.asarray(r.mb)


def _run_ref(stream, cfg):
    w = jnp.where(stream.valid, stream.weight, 0.0)
    a, mb = substream_match_ref(stream.src, stream.dst, w, cfg.thresholds(), cfg.n)
    return np.asarray(a), np.asarray(mb).astype(bool)


def _run_waves_xla(stream, cfg):
    r = mwm_waves(stream, cfg)
    return np.asarray(r.assigned), np.asarray(r.mb)


def _run_pallas(schedule, **kw):
    def run(stream, cfg):
        r = substream_match(stream, cfg, interpret=True, schedule=schedule, **kw)
        return np.asarray(r.assigned), np.asarray(r.mb)

    return run


ENGINES = {
    "ref": _run_ref,
    "pallas_edges": _run_pallas("edges"),
    "mega": _run_pallas("mega"),
    "mega_seg1": _run_pallas("mega", seg_block=1),
    "waves_xla": _run_waves_xla,
}


def _manual_clean(stream, cfg):
    """Independently drop the bad edges with plain numpy comparisons."""
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    w = np.asarray(stream.weight)
    with np.errstate(invalid="ignore"):
        good = (
            (src >= 0) & (src < cfg.n) & (dst >= 0) & (dst < cfg.n)
            & np.isfinite(w) & (w >= 0)
        )
    return EdgeStream(
        src=stream.src, dst=stream.dst, weight=stream.weight,
        valid=jnp.asarray(np.asarray(stream.valid) & good),
    )


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("graph", sorted(DIRTY_ZOO))
def test_engines_bit_identical_after_sanitize(graph, engine):
    stream, cfg = DIRTY_ZOO[graph]()
    clean, report = validate_stream(stream, cfg.n, policy="sanitize")
    assert (report.num_dropped == 0) == (graph in CLEAN_DIRT)
    want_a, want_mb = _run_scan(clean, cfg)
    got_a, got_mb = ENGINES[engine](clean, cfg)
    assert (got_a == want_a).all(), f"{engine} diverges on sanitized {graph}"
    assert (got_mb == want_mb).all(), f"{engine} diverges on sanitized {graph}"
    # and sanitize agrees with an independent manual clean
    manual_a, manual_mb = _run_scan(_manual_clean(stream, cfg), cfg)
    assert (want_a == manual_a).all()
    assert (want_mb == manual_mb).all()


@pytest.mark.parametrize("graph", sorted(DIRTY_ZOO))
def test_strict_rejects_exactly_the_dirty_graphs(graph):
    stream, cfg = DIRTY_ZOO[graph]()
    if graph in CLEAN_DIRT:
        out, report = validate_stream(stream, cfg.n, policy="strict")
        assert out is stream and report.ok
    else:
        with pytest.raises(StreamValidationError):
            validate_stream(stream, cfg.n, policy="strict")


@pytest.mark.parametrize("graph", sorted(DIRTY_ZOO))
def test_postconditions_hold_on_sanitized_results(graph):
    stream, cfg = DIRTY_ZOO[graph]()
    clean, _ = validate_stream(stream, cfg.n, policy="sanitize")
    res = mwm_scan(clean, cfg)
    merged = merge_host(clean, res, cfg)
    exact = exact_mwm_weight(clean)
    check_matching(res, clean, cfg, merged=merged, exact_weight=exact)
    if exact > 0:
        assert matching_weight(clean, merged) > 0


def test_validation_report_counters_shape():
    stream, cfg = DIRTY_ZOO["everything_at_once"]()
    _, report = validate_stream(stream, cfg.n, policy="sanitize")
    counters = report.counters()
    assert counters["guard.dropped_edges"] == report.num_dropped > 0
    assert counters["guard.num_problems"] == len(report.problems) > 0
    for p in report.problems:
        assert counters[f"guard.fault.{p.kind}"] == p.count
    # stream_problems is pure and reports the same faults
    kinds = {
        p.kind
        for p in stream_problems(
            np.asarray(stream.src), np.asarray(stream.dst),
            np.asarray(stream.weight), np.asarray(stream.valid), cfg.n,
        )
    }
    assert kinds == {p.kind for p in report.problems}
    assert kinds == {"id_out_of_range", "nonfinite_weight", "negative_weight"}


# ---------------------------------------------------------------------------
# from_numpy cast guards (satellite: no silent int64 wrap / NaN propagation)
# ---------------------------------------------------------------------------


def test_from_numpy_strict_rejects_id_overflow():
    with pytest.raises(StreamValidationError, match="id_overflow"):
        EdgeStream.from_numpy(np.array([2**40], np.int64), [1], [1.0])


def test_from_numpy_strict_rejects_nonfinite_weights():
    for bad in (np.nan, np.inf, 1e40):  # 1e40 overflows the float32 cast
        with pytest.raises(StreamValidationError, match="nonfinite_weight"):
            EdgeStream.from_numpy([0], [1], np.array([bad]))


def test_from_numpy_sanitize_drops_unrepresentable():
    s = EdgeStream.from_numpy(
        np.array([2**40, 1, 2], np.int64), [1, 2, 3],
        np.array([1.0, np.nan, 3.0]), policy="sanitize",
    )
    assert np.asarray(s.valid).tolist() == [False, False, True]
    assert int(np.asarray(s.src)[2]) == 2
    assert float(np.asarray(s.weight)[2]) == 3.0


def test_from_numpy_off_is_legacy_wrap():
    s = EdgeStream.from_numpy(
        np.array([2**32], np.int64), [1], np.array([np.inf]), policy="off"
    )
    assert int(np.asarray(s.src)[0]) == 0  # wrapped, as before
    assert np.isinf(np.asarray(s.weight)[0])


def test_from_numpy_clean_int32_fast_path_unchanged():
    s = EdgeStream.from_numpy(
        np.array([0, 1], np.int32), np.array([1, 2], np.int32),
        np.array([1.5, 2.5], np.float32), n_pad=4,
    )
    assert np.asarray(s.valid).tolist() == [True, True, False, False]
    assert np.asarray(s.weight).tolist() == [1.5, 2.5, 0.0, 0.0]


def test_from_numpy_rejects_mismatched_lengths():
    with pytest.raises(ValueError, match="lengths differ"):
        EdgeStream.from_numpy([0, 1], [1], [1.0, 2.0])


def test_from_numpy_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        EdgeStream.from_numpy([0], [1], [1.0], policy="lenient")


# ---------------------------------------------------------------------------
# Degenerate streams: m == 0, all-dropped, n == 0 (satellite hardening)
# ---------------------------------------------------------------------------


def _assert_empty_result(res, stream, cfg):
    assert res.assigned.shape == (stream.num_edges,)
    assert (np.asarray(res.assigned) == -1).all()
    assert np.asarray(res.mb).shape == (cfg.n, cfg.L)
    assert not np.asarray(res.mb).any()
    merged = merge_host(stream, res, cfg)
    assert merged.shape == (0,) and merged.dtype == np.int64
    assert matching_weight(stream, merged) == 0.0
    assert not np.asarray(merge_device(stream, res, cfg)).any()
    check_matching(res, stream, cfg, merged=merged, exact_weight=0.0)


@pytest.mark.parametrize(
    "case", ["m0", "all_dropped", "n0", "n0_with_padding_edges"]
)
def test_degenerate_streams_well_formed_everywhere(case):
    if case == "m0":
        stream, cfg = _dirty(8, [], [], [])
    elif case == "all_dropped":
        stream, cfg = _dirty(8, [0, 1, 5], [1, 2, 5], [np.nan, -1.0, 2.0])
        stream, _ = validate_stream(stream, cfg.n, policy="sanitize")
        # the self-loop (5,5) survives sanitize but never matches
    elif case == "n0":
        stream, cfg = _dirty(0, [], [], [])
    else:
        stream = EdgeStream.from_numpy([], [], [], n_pad=6)
        cfg = SubstreamConfig(n=0, L=4)
    _assert_empty_result(mwm_scan(stream, cfg), stream, cfg)
    _assert_empty_result(mwm_waves(stream, cfg), stream, cfg)
    for schedule, seg_block in (("edges", None), ("mega", 1), ("mega", None)):
        res = substream_match(
            stream, cfg, interpret=True, schedule=schedule, seg_block=seg_block
        )
        _assert_empty_result(res, stream, cfg)


def test_n0_with_valid_edges_is_a_validation_problem():
    stream, _ = _dirty(8, [0, 1], [1, 2], [1.0, 2.0])
    problems = stream_problems(
        np.asarray(stream.src), np.asarray(stream.dst),
        np.asarray(stream.weight), np.asarray(stream.valid), 0,
    )
    assert [p.kind for p in problems] == ["empty_vertex_space"]
    with pytest.raises(StreamValidationError, match="empty_vertex_space"):
        validate_stream(stream, 0, policy="strict")
    clean, report = validate_stream(stream, 0, policy="sanitize")
    assert report.num_dropped == 2
    assert not np.asarray(clean.valid).any()

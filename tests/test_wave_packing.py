"""Property suite for the fill-packed wave scheduler.

The packer's contract, on top of the generic wave invariants:

* every wave — and therefore every packed segment row — is
  vertex-disjoint;
* conflicting edges keep their processing order across waves;
* every edge is placed exactly at its greedy conflict depth, which
  makes the wave count provably minimal;
* the fill-packed [num_segments, SEG] layout carries padding only at
  each wave's tail segment, so the fill never depends on wave-size skew;
* both the packed (uint8 bit-plane) and unpacked (int8) engine layouts
  stay bit-identical to the sequential scan oracle in ``assigned`` and
  ``mb`` — including self-loops, duplicate edges, L % 8 != 0, and
  single-edge streams.
"""
import contextlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import EdgeStream, SubstreamConfig, mwm_scan, mwm_waves
from repro.graph.waves import (
    SEG,
    block_aligned_layout,
    check_block_aligned,
    check_schedule,
    greedy_depths,
    wave_schedule,
)
from repro.kernels.substream_match.ops import (
    SLOT_SMEM_BYTES,
    SMEM_PER_CORE,
    VMEM_PER_CORE,
    mega_plan,
    substream_match,
)
from repro.obs import trace as obs_trace

SETTINGS = dict(max_examples=15, deadline=None)


def _stream(draw, max_n=48, max_m=150):
    """Streams biased to the packer edge cases: self-loops and duplicate
    edges (both kept on purpose), padding edges, L % 8 != 0."""
    n = draw(st.integers(4, max_n))
    m = draw(st.integers(1, max_m))
    L = draw(st.sampled_from([1, 4, 9, 16, 33]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    cfg = SubstreamConfig(n=n, L=L, eps=0.1)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if m > 4 and draw(st.booleans()):  # force exact duplicate edges
        src[m // 2] = src[0]
        dst[m // 2] = dst[0]
    if m > 2 and draw(st.booleans()):  # force a self-loop
        dst[m // 3] = src[m // 3]
    w = rng.uniform(0.5, cfg.w_max * 1.1, m).astype(np.float32)
    pad = draw(st.sampled_from([0, 7]))
    return EdgeStream.from_numpy(src, dst, w, n_pad=m + pad), cfg


@given(st.data())
@settings(**SETTINGS)
def test_packer_invariants_uncapped(data):
    """Packing = exact conflict depth: wave-count minimal, and
    the packed layout groups each wave's members contiguously with
    padding only at its tail segment."""
    stream, _ = _stream(data.draw)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    sch = wave_schedule(src, dst, valid=valid)
    check_schedule(sch, src, dst, valid)
    depths = greedy_depths(src, dst, valid=valid)
    assert (sch.wave == depths).all(), "packing must equal depth"
    # wave count floor: the longest conflict chain; also >= max vertex
    # multiplicity, so no vertex-disjoint decomposition can do better
    assert sch.num_waves == (int(depths.max()) + 1 if valid.any() else 0)
    # fill-packed accounting: one partially-filled segment max per wave
    sizes = sch.wave_sizes()
    assert sch.num_segments == int((-(-sizes // SEG)).sum())
    assert sch.slots.shape == (sch.num_segments, SEG)
    assert sch.num_scheduled == int(valid.sum())
    assert sch.schedule_seconds >= 0.0 and sch.pack_seconds >= 0.0


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_packed_schedule_bit_identity(data):
    """Packed-layout engine results == the sequential scan oracle, for
    packed and unpacked bit layouts."""
    stream, cfg = _stream(data.draw, max_n=32, max_m=90)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    want = mwm_scan(stream, cfg)
    sch = wave_schedule(src, dst, valid=valid)
    got_xla = mwm_waves(stream, cfg, schedule=sch)
    seg1 = dict(schedule="mega", seg_block=1, waves=sch)
    got_p = substream_match(stream, cfg, packed=True, **seg1)
    got_u = substream_match(stream, cfg, packed=False, **seg1)
    for got in (got_xla, got_p, got_u):
        assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
        assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def test_single_edge_stream():
    stream = EdgeStream.from_numpy([0], [1], [3.0])
    cfg = SubstreamConfig(n=4, L=9, eps=0.1)
    sch = wave_schedule(np.asarray(stream.src), np.asarray(stream.dst))
    assert sch.num_waves == 1 and sch.num_segments == 1
    assert sch.fill == 1 / SEG
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg, schedule="mega", seg_block=1, waves=sch)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def test_fill_beats_global_padding_on_skew():
    """The motivating case: one hub wave much wider than the rest. The
    old layout padded every wave to the hub width (fill -> 1/max);
    fill-packing bounds the loss at < SEG slots per wave."""
    hub = np.repeat(np.arange(1, 65), 1)  # 64 disjoint edges, one wave
    src = np.concatenate([2 * hub, np.zeros(32, np.int64)])
    dst = np.concatenate([2 * hub + 1, np.arange(200, 232)])
    sch = wave_schedule(src, dst)
    # wave 0 has 65 edges (64 disjoint + first hub edge), then 31 hub
    # waves of one edge each; packed fill stays high regardless
    assert sch.max_wave_size >= 64
    assert sch.fill >= len(src) / (len(src) + SEG * sch.num_waves)
    assert sch.fill > 0.25


def test_packer_determinism():
    rng = np.random.default_rng(3)
    src = rng.integers(0, 30, 200)
    dst = rng.integers(0, 30, 200)
    a = wave_schedule(src, dst)
    b = wave_schedule(src, dst)
    for f in ("wave", "order", "offsets", "slots", "seg_offsets"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f


@given(st.data())
@settings(**SETTINGS)
def test_block_aligned_offsets_invariants(data):
    """Block-aligned re-layout: offsets monotone and seg_block-aligned,
    every scheduled slot covered exactly once, padding rows only at each
    wave's tail (the last partial tile is pure -1 padding, which the
    mega host prep remaps to the sacrificial row n_pad)."""
    stream, _ = _stream(data.draw)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    sch = wave_schedule(src, dst, valid=valid)
    sb = data.draw(st.sampled_from([1, 2, 3, 4, 8]))
    layout = block_aligned_layout(sch, sb)
    check_block_aligned(layout, sch)  # coverage, order, tail-only padding
    offs = layout.seg_offsets
    assert offs[0] == 0 and offs[-1] == layout.num_segments
    assert (np.diff(offs) >= 0).all()
    assert (offs % sb == 0).all()
    assert layout.num_segments % sb == 0
    assert layout.num_tiles * sb == layout.num_segments
    # alignment only ever adds padding: fill can't exceed the source's
    assert layout.fill <= sch.fill + 1e-12
    # each wave pays < one full tile of padding rows
    segc = np.diff(sch.seg_offsets)
    assert ((np.diff(offs) - segc) < sb).all()
    # seg_block=1 is the identity re-layout
    if sb == 1:
        assert np.array_equal(layout.slots, sch.slots)


@given(st.data())
@settings(**SETTINGS)
def test_mega_plan_double_buffer_accounting(data):
    """WavePlan totals under double-buffering: the plan charges exactly
    2x one buffer of the slot pipeline, the double-buffered slot and
    assigned blocks fit SMEM, and the bit block with its headroom fits
    VMEM_PER_CORE."""
    stream, cfg = _stream(data.draw, max_n=40, max_m=120)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    sch = wave_schedule(src, dst, valid=valid)
    sb = data.draw(st.sampled_from([1, 2, 4]))
    layout = block_aligned_layout(sch, sb)
    packed = data.draw(st.booleans())
    plan = mega_plan(cfg.n, cfg.L, layout, packed=packed)
    assert plan.seg_block == sb
    assert plan.num_tiles == layout.num_tiles
    assert plan.gather_bytes == 2 * plan.tile_bytes, "double-buffer = 2x tile"
    assert plan.block_e == plan.tiles_per_block * sb * plan.seg
    assert plan.gather_bytes == plan.block_e * SLOT_SMEM_BYTES
    assert plan.gather_bytes <= SMEM_PER_CORE
    assert plan.vmem_limit <= VMEM_PER_CORE
    # the resident bit block itself is within the reserved budget
    assert plan.nbytes == plan.n_pad * plan.width


def test_mega_plan_rejects_oversized_tiles():
    """A seg_block so large the double-buffered tiles can't fit VMEM is
    rejected with the knob named."""
    src = np.arange(0, 4000, 2)
    dst = np.arange(1, 4000, 2)
    sch = wave_schedule(src, dst)
    layout = block_aligned_layout(sch, 32768)
    with pytest.raises(ValueError, match="seg_block"):
        mega_plan(64, 32, layout)


@pytest.mark.parametrize("m", [1, 7, 8, 9, 40000])
def test_conflict_free_stream_packs_full_segments(m):
    """All-independent edges: one wave, ceil(m / SEG) segments, and the
    batched depth passes stay near-linear (no per-edge Python loop)."""
    src = np.arange(0, 2 * m, 2)
    dst = np.arange(1, 2 * m, 2)
    sch = wave_schedule(src, dst)
    assert sch.num_waves == 1
    assert sch.num_segments == -(-m // SEG)
    assert sch.fill == m / (sch.num_segments * SEG)


def _reference_schedule(src, dst, valid=None, order=None, seg=SEG):
    """The schedule as a plain two-step computation: the
    sequential ``greedy_depths`` oracle, then a stable argsort of the
    depths and a bincount for the wave-major order and the slot fill.
    Vertex ids are relabelled densely first (depths do not depend on
    the labels), so ids near 2**31 need no 2**31-entry table."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    m = src.shape[0]
    ids, dense = np.unique(np.concatenate([src, dst]), return_inverse=True)
    depth = greedy_depths(dense[:m], dense[m:], valid=valid, order=order)
    scheduled = np.nonzero(depth >= 0)[0]
    order_out = scheduled[np.argsort(depth[scheduled], kind="stable")]
    num_waves = int(depth.max()) + 1 if scheduled.size else 0
    counts = np.bincount(depth[scheduled], minlength=num_waves)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    seg_offsets = np.concatenate([[0], np.cumsum(-(-counts // seg))])
    slots = np.full((int(seg_offsets[-1]), seg), -1, np.int64)
    for k in range(num_waves):
        members = order_out[offsets[k] : offsets[k + 1]]
        rows = slots[seg_offsets[k] : seg_offsets[k + 1]].reshape(-1)
        rows[: members.size] = members
        slots[seg_offsets[k] : seg_offsets[k + 1]] = rows.reshape(-1, seg)
    arrays = dict(
        wave=depth, order=order_out, offsets=offsets, slots=slots,
        seg_offsets=seg_offsets,
    )
    return {k: np.asarray(a, np.int64).astype(np.int32) for k, a in arrays.items()}


def _random_stream(rng, n, m):
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    dst[::7] = src[::7]  # self-loops
    src[m // 2 :: 5] = src[0]  # duplicate pairs
    dst[m // 2 :: 5] = dst[0]
    return src, dst


def _equivalence_case(name):
    """(src, dst, valid, order, seg) of one named stream."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "loops_and_duplicates":
        src, dst = _random_stream(rng, 40, 600)
        return src, dst, None, None, SEG
    if name == "padding":
        src, dst = _random_stream(rng, 40, 600)
        return src, dst, rng.random(600) > 0.2, None, SEG
    if name == "explicit_order":
        src, dst = _random_stream(rng, 40, 600)
        return src, dst, rng.random(600) > 0.2, rng.permutation(600), SEG
    if name == "explicit_order_subset":
        src, dst = _random_stream(rng, 30, 400)
        return src, dst, None, rng.permutation(400)[:250], 3
    if name == "dense_multigraph":
        src, dst = _random_stream(rng, 4, 500)
        return src, dst, None, None, SEG
    if name == "all_self_loops":
        src = rng.integers(0, 20, 300)
        return src, src.copy(), None, None, SEG
    if name == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), None, None, SEG
    if name == "one_edge":
        return np.array([5]), np.array([9]), None, None, SEG
    if name == "one_padding_edge":
        return np.array([5]), np.array([9]), np.array([False]), None, SEG
    if name == "star":  # one hub: a conflict chain as deep as m
        m = 700
        leaves = rng.permutation(np.arange(1, m + 1))
        return np.zeros(m, np.int64), leaves, None, None, SEG
    if name == "ids_near_int32_max":
        top = 2**31 - 1
        src, dst = _random_stream(rng, 64, 600)
        return top - src, top - dst, rng.random(600) > 0.1, None, SEG
    raise KeyError(name)


EQUIVALENCE_CASES = [
    "loops_and_duplicates", "padding", "explicit_order",
    "explicit_order_subset", "dense_multigraph", "all_self_loops", "empty",
    "one_edge", "one_padding_edge", "star", "ids_near_int32_max",
]


@pytest.mark.parametrize("name", EQUIVALENCE_CASES)
def test_uncapped_schedule_equals_reference_pack(name):
    """Every array of the schedule, dtypes included, equals the
    sequential depths packed by a stable argsort: the peel's wave-major
    order and the packed-key links change how, never what."""
    src, dst, valid, order, seg = _equivalence_case(name)
    sch = wave_schedule(src, dst, valid=valid, order=order, seg=seg)
    want = _reference_schedule(src, dst, valid=valid, order=order, seg=seg)
    assert sch.num_edges == len(src)
    for field, expected in want.items():
        got = getattr(sch, field)
        assert got.dtype == expected.dtype == np.int32, field
        assert got.shape == expected.shape, field
        np.testing.assert_array_equal(got, expected, err_msg=field)
    if name == "star":
        assert sch.num_waves == src.shape[0]


def test_assign_spans_links_and_peel(monkeypatch):
    """``wave_schedule.assign`` holds the two child spans ``links`` and
    ``peel``, and ``links`` holds the copy of the links to the host; with
    telemetry off nothing is recorded or annotated."""
    opened = []

    def annotation(name):
        opened.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(obs_trace, "_annotation", annotation)
    src, dst = _random_stream(np.random.default_rng(3), 40, 300)
    wave_schedule(src, dst)
    wave_schedule(src, dst, telemetry=obs.DISABLED)
    assert opened == []

    tel = obs.Telemetry()
    wave_schedule(src, dst, telemetry=tel)
    spans = [e for e in tel.tracer.events if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in spans}
    assert [e["name"] for e in spans] == [
        "wave_schedule.prepare", "copy.d2h", "wave_schedule.links",
        "wave_schedule.peel", "wave_schedule.assign", "wave_schedule.pack",
        "wave_schedule.emit",
    ]
    for child in ("wave_schedule.links", "wave_schedule.peel"):
        assert by_name[child]["args"]["parent"] == "wave_schedule.assign"
    assert by_name["copy.d2h"]["args"]["parent"] == "wave_schedule.links"
    assert by_name["copy.d2h"]["args"]["what"] == "links"
    assert by_name["wave_schedule.assign"]["args"]["parent"] is None
    assert {"wave_schedule.links", "wave_schedule.peel"} <= set(opened)

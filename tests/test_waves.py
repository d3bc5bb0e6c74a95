"""Wave scheduler + wave-vectorized engine properties.

The wave pipeline's contract: decomposing the stream into vertex-disjoint
waves and processing each wave simultaneously is *bit-identical* to the
sequential 1-edge scan (greedy matching is confluent over vertex-disjoint
edges) — across the XLA reference (`mwm_waves`), the packed and unpacked
Pallas megakernel at one segment per tile (`substream_match(schedule=
"mega", seg_block=1)`), the rounds engine with wave offsets, and the
blocked lexicographic pre-order.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    EdgeStream,
    SubstreamConfig,
    lexicographic_order,
    merge_host,
    mwm_blocked,
    mwm_rounds,
    mwm_scan,
    mwm_waves,
    pack_bits,
    permute_stream,
)
from repro.graph.waves import (
    WaveSchedule,
    block_aligned_layout,
    check_schedule,
    slot_arrays,
    wave_schedule,
)
from repro.kernels.substream_match.ops import (
    EPOCH_ENGINES,
    SLOT_SMEM_BYTES,
    SMEM_PER_CORE,
    VMEM_BIT_BUDGET,
    VMEM_PER_CORE,
    WavePlan,
    match_epochs,
    mega_plan,
    resolve_interpret,
    substream_match,
)

SETTINGS = dict(max_examples=15, deadline=None)

#: The megakernel at one segment per tile: one trip per schedule row.
SEG1 = dict(schedule="mega", seg_block=1)


def _stream(draw, max_n=48, max_m=150):
    """Streams biased to the wave edge cases: self-loops and duplicate
    edges (both kept on purpose), padding edges, L % 8 != 0."""
    n = draw(st.integers(4, max_n))
    m = draw(st.integers(1, max_m))
    L = draw(st.sampled_from([1, 4, 9, 16, 33]))
    eps = draw(st.sampled_from([0.1, 0.5]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    cfg = SubstreamConfig(n=n, L=L, eps=eps)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if m > 4 and draw(st.booleans()):  # force exact duplicate edges
        src[m // 2] = src[0]
        dst[m // 2] = dst[0]
    w = rng.uniform(0.5, cfg.w_max * 1.1, m).astype(np.float32)
    pad = draw(st.sampled_from([0, 7]))
    return EdgeStream.from_numpy(src, dst, w, n_pad=m + pad), cfg


@given(st.data())
@settings(**SETTINGS)
def test_wave_scheduler_invariants(data):
    """Every wave is vertex-disjoint; conflicting edges keep stream order
    across waves; order/offsets/slots agree; padding stays unscheduled."""
    stream, _ = _stream(data.draw)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    sch = wave_schedule(src, dst, valid=valid)
    check_schedule(sch, src, dst, valid)
    assert sch.num_scheduled == int(valid.sum())
    assert sch.width % 8 == 0
    # the permutation is order-preserving within each wave (stable)
    for k in range(sch.num_waves):
        members = sch.order[sch.offsets[k] : sch.offsets[k + 1]]
        assert (np.diff(members) > 0).all()


@given(st.data())
@settings(**SETTINGS)
def test_mwm_waves_equals_scan(data):
    stream, cfg = _stream(data.draw)
    want = mwm_scan(stream, cfg)
    got = mwm_waves(stream, cfg)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


@given(st.data())
@settings(max_examples=8, deadline=None)
def test_wave_kernel_equals_scan(data):
    """The megakernel at one segment per tile is bit-identical to
    mwm_scan for both layouts, and the two layouts ship identical packed
    words."""
    stream, cfg = _stream(data.draw, max_n=32, max_m=80)
    want = mwm_scan(stream, cfg)
    got_p = substream_match(stream, cfg, packed=True, **SEG1)
    got_u = substream_match(stream, cfg, packed=False, **SEG1)
    assert got_p.is_packed and not got_u.is_packed
    assert (np.asarray(got_p.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got_u.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got_p.mb) == np.asarray(want.mb)).all()
    assert (np.asarray(got_u.mb) == np.asarray(want.mb)).all()
    assert (np.asarray(got_p.mb_packed) == np.asarray(pack_bits(want.mb))).all()


@given(st.data())
@settings(**SETTINGS)
def test_rounds_with_waves_equals_scan(data):
    stream, cfg = _stream(data.draw)
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
    )
    want = mwm_scan(stream, cfg)
    got = mwm_rounds(stream, cfg, waves=sch)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()
    packed = mwm_rounds(stream, cfg, waves=sch, packed=True)
    assert packed.is_packed
    assert (np.asarray(packed.mb) == np.asarray(want.mb)).all()


def test_rounds_waves_rejects_max_rounds(rng):
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 16, 40, 8, 0.1)
    sch = wave_schedule(np.asarray(stream.src), np.asarray(stream.dst))
    with pytest.raises(ValueError, match="max_rounds"):
        mwm_rounds(stream, cfg, max_rounds=3, waves=sch)


def test_scheduler_handles_conflict_free_streams_at_scale():
    """All-independent edges are one wave of m edges, packed into full
    segments: the peel resolves the whole stream in its first pass."""
    m = 40_000
    src = np.arange(0, 2 * m, 2)
    dst = np.arange(1, 2 * m, 2)
    sch = wave_schedule(src, dst)
    assert sch.num_waves == 1
    assert sch.wave_sizes().tolist() == [m]
    assert sch.num_segments == m // sch.width and sch.fill == 1.0
    np.testing.assert_array_equal(sch.order, np.arange(m))


def test_wave_kernel_blocked_order(rng):
    """Waves over the lexicographic blocked order: identical to the
    blocked scan reference, end to end through the merge."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 40, 200, 17, 0.1, self_loops=True)
    want = mwm_blocked(stream, cfg, K=8, backend="scan")
    got = mwm_blocked(stream, cfg, K=8, backend="pallas", **SEG1)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()
    assert (merge_host(stream, got, cfg) == merge_host(stream, want, cfg)).all()


def test_wave_schedule_respects_explicit_order(rng):
    """A schedule built over a permuted order serializes conflicts in
    *that* order: running mwm_waves on the permuted stream with the
    stream-order schedule of the permutation matches the permuted scan."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 24, 120, 16, 0.1, self_loops=True)
    order = np.asarray(lexicographic_order(stream, K=4))
    blocked = permute_stream(stream, order)
    # schedule the *original* stream under the lexicographic order...
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
        order=order,
    )
    check_schedule(sch, np.asarray(stream.src), np.asarray(stream.dst), order=order)
    # ...and the schedule of the permuted stream must induce the same waves
    sch_b = wave_schedule(
        np.asarray(blocked.src),
        np.asarray(blocked.dst),
        valid=np.asarray(blocked.valid),
    )
    assert (sch.wave[order] == sch_b.wave).all()


def test_reused_schedule_across_L(rng):
    """One schedule serves any (L, eps): it depends only on endpoints."""
    from tests.conftest import make_stream

    stream, _ = make_stream(rng, 30, 150, 16, 0.1)
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
    )
    for L, eps in [(1, 0.5), (9, 0.1), (64, 0.05)]:
        cfg = SubstreamConfig(n=30, L=L, eps=eps)
        want = mwm_scan(stream, cfg)
        got = substream_match(stream, cfg, waves=sch, **SEG1)
        assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
        assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def test_stale_schedule_rejected(rng):
    """A schedule whose waves are no longer vertex-disjoint for the
    stream (e.g. the stream was permuted after scheduling) must raise,
    not silently corrupt the scatter-add."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 24, 150, 8, 0.1)
    sch = wave_schedule(
        np.asarray(stream.src), np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
    )
    perm = np.random.default_rng(1).permutation(stream.num_edges)
    shuffled = permute_stream(stream, perm)
    with pytest.raises(ValueError, match="disjoint|cover"):
        substream_match(shuffled, cfg, waves=sch, **SEG1)
    with pytest.raises(ValueError, match="disjoint|cover"):
        mwm_waves(shuffled, cfg, schedule=sch)
    # coverage mismatch: schedule built ignoring the valid mask
    padded, cfg2 = make_stream(rng, 24, 100, 8, 0.1, pad=9)
    sch_all = wave_schedule(np.asarray(padded.src), np.asarray(padded.dst))
    with pytest.raises(ValueError, match="valid"):
        mwm_waves(padded, cfg2, schedule=sch_all)


def test_schedule_stream_mismatch_raises(rng):
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 16, 50, 8, 0.1)
    other, _ = make_stream(rng, 16, 70, 8, 0.1)
    sch = wave_schedule(np.asarray(other.src), np.asarray(other.dst))
    with pytest.raises(ValueError, match="schedule"):
        substream_match(stream, cfg, waves=sch, **SEG1)
    with pytest.raises(ValueError, match="schedule"):
        mwm_waves(stream, cfg, schedule=sch)
    with pytest.raises(ValueError, match="schedule"):
        substream_match(stream, cfg, schedule="zigzag")


def test_slot_arrays_padding_encoding(rng):
    src = np.array([1, 2, 3, 1])
    dst = np.array([2, 3, 4, 5])
    w = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    sch = wave_schedule(src, dst)
    u, v, ws, ok = slot_arrays(sch, src, dst, w)
    assert u.shape == (sch.num_segments, sch.width)
    # padding slots can never match: self-loop at vertex 0 with weight 0
    # (the Pallas path additionally remaps them to the sacrificial row)
    assert (u[~ok] == 0).all() and (v[~ok] == 0).all() and (ws[~ok] == 0).all()
    assert ok.sum() == 4


def test_seg1_plan_accounting(rng):
    """The plan of the megakernel at one segment per tile: one trip per
    schedule row, ``block_s`` rows per grid program."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 100, 400, 48, 0.1)
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
    )
    layout = block_aligned_layout(sch, 1)
    for packed in (True, False):
        plan = mega_plan(cfg.n, cfg.L, layout, packed=packed)
        assert isinstance(plan, WavePlan)
        assert plan.seg == sch.width
        assert plan.num_waves == sch.num_waves
        assert plan.num_segments == sch.num_segments
        assert plan.block_e == plan.block_s * plan.seg
        # the slot pipeline's SMEM scales with the segment block, not
        # the largest wave, and the bit block is the only VMEM it asks for
        assert plan.gather_bytes == plan.block_s * sch.width * SLOT_SMEM_BYTES
        assert 0 < plan.gather_bytes <= SMEM_PER_CORE
        assert plan.nbytes <= VMEM_BIT_BUDGET
        assert plan.vmem_limit <= VMEM_PER_CORE
    # oversized segment tiles must be rejected, pointing at seg
    huge = WaveSchedule(
        wave=np.zeros(1, np.int32),
        order=np.zeros(1, np.int32),
        offsets=np.array([0, 1], np.int32),
        slots=np.zeros((1, 2**22), np.int32),
        seg_offsets=np.array([0, 1], np.int32),
        num_edges=1,
    )
    with pytest.raises(ValueError, match="seg"):
        mega_plan(cfg.n, cfg.L, block_aligned_layout(huge, 1), packed=True)
    # tiles per program that overflow the stream buffers are named
    with pytest.raises(ValueError, match="tiles_per_block"):
        mega_plan(cfg.n, cfg.L, layout, packed=True, tiles_per_block=2**24)


def test_seg_block_one_layout_is_the_schedule(rng):
    """At one segment per tile the block-aligned layout is the schedule's
    own slot layout, and a grid program holds ``block_s`` whole rows: the
    megakernel at ``seg_block=1`` runs exactly the one-segment-per-trip
    program."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 300, 4000, 64, 0.1, self_loops=True, pad=9)
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
    )
    layout = block_aligned_layout(sch, 1)
    np.testing.assert_array_equal(layout.slots, sch.slots)
    np.testing.assert_array_equal(layout.seg_offsets, sch.seg_offsets)
    assert layout.num_tiles == sch.num_segments
    plan = mega_plan(cfg.n, cfg.L, layout)
    assert plan.seg_block == 1 and plan.block_s == plan.tiles_per_block
    assert plan.block_e == plan.block_s * plan.seg
    assert plan.gather_bytes == plan.block_e * SLOT_SMEM_BYTES


@pytest.mark.parametrize("entry", ["substream_match", "match_epochs"])
def test_retired_waves_engine_rejected(rng, entry):
    """The one-segment Pallas engine is the megakernel at ``seg_block=1``;
    the old engine name is refused with the names that remain."""
    from tests.conftest import make_stream

    stream, cfg = make_stream(rng, 16, 40, 8, 0.1)
    with pytest.raises(ValueError, match="edges.*mega") as err:
        if entry == "substream_match":
            substream_match(stream, cfg, schedule="waves")
        else:
            match_epochs(stream, cfg, engine="waves")
    assert "'waves'" in str(err.value)
    assert "waves" not in EPOCH_ENGINES


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_wave_path_vmem_budget_enforced(rng, packed):
    """An over-budget *bit block* reports the rounds/partitioning error,
    not the slot-buffer one (that is only for oversized tiles)."""
    from tests.conftest import make_stream

    stream, _ = make_stream(rng, 16, 40, 4, 0.1)
    big = SubstreamConfig(n=100_000_000, L=512, eps=0.1)
    with pytest.raises(ValueError, match="rounds"):
        substream_match(stream, big, packed=packed, **SEG1)


def test_resolve_interpret_auto():
    import jax

    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_empty_and_degenerate_streams():
    # single self-loop: one wave, never matches
    stream = EdgeStream.from_numpy([3], [3], [5.0])
    cfg = SubstreamConfig(n=8, L=8, eps=0.1)
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg, **SEG1)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    # all-padding stream: zero waves scheduled
    padded = EdgeStream.from_numpy([0], [1], [2.0], n_pad=4)
    padded = EdgeStream(
        src=padded.src, dst=padded.dst, weight=padded.weight,
        valid=np.zeros(4, bool),
    )
    sch = wave_schedule(
        np.asarray(padded.src), np.asarray(padded.dst),
        valid=np.asarray(padded.valid),
    )
    assert sch.num_waves == 0 and sch.num_scheduled == 0
    got = substream_match(padded, cfg, waves=sch, **SEG1)
    assert (np.asarray(got.assigned) == -1).all()
    assert not np.asarray(got.mb).any()

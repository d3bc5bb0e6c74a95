"""Fill-packed wave decomposition of an edge stream into conflict-free batches.

The paper's edge processor (§4.4) consumes one edge per cycle because
consecutive stream edges may share a vertex and therefore race on the
same matching-bit row. But greedy matching w.r.t. a fixed edge order is
*confluent* over vertex-disjoint edges: if no two edges of a batch share
an endpoint, processing the batch in any order — or simultaneously —
yields bit-identical matching bits and recorded lists. So the stream can
be cut into **waves** such that every wave is vertex-disjoint while
conflicting edges keep their stream order across waves.

Scheduling (the tentpole of this module) puts every edge into the wave
of its **conflict depth**: one past the wave of every earlier edge
sharing an endpoint. That assignment is *provably minimal*: the wave
count equals the longest conflict chain (≥ the maximum vertex
multiplicity — every edge at the hub vertex needs its own wave), so no
valid vertex-disjoint decomposition can use fewer waves. It has two
steps. The conflict links (each edge's next edge at either endpoint)
are built on the device that holds the stream, by two sorts of its
endpoints (:func:`_links_device`); only the links come back to the
host. A vectorized numpy indegree peel of that 2-predecessor DAG then
resolves one wave per pass and keeps each pass's frontier, so it hands
the pack the wave-major order itself: the pack needs no sort (one sort
of packed (wave, position) keys when an explicit ``order`` permuted the
stream).

Layout (where the "fill-packed" in the title lives): waves are *not*
padded to one global maximum width. They are packed back-to-back into
fixed-size **segments** of ``SEG`` slots (a wave of size s occupies
``ceil(s / SEG)`` segments; only its last segment carries padding), so
``slots`` is ``[num_segments, SEG]`` and the fill — the fraction of
slots holding a real edge — stays high regardless of wave-size skew.
Each segment is a *subset* of one wave and therefore vertex-disjoint
itself: every consumer that processed "one slots-row at a time"
(the XLA wave scan, the Pallas megakernel, rounds-with-waves) keeps
its row-major contract unchanged, with per-row traffic proportional to
``SEG`` instead of the largest wave.

This module is pure scheduling — a stream in (host or device arrays),
a numpy schedule out, no dependency on :mod:`repro.core` — so the XLA
reference (`repro.core.matching.mwm_waves`), the Pallas kernels
(`repro.kernels.substream_match`) and the rounds engine
(`repro.core.rounds`) share one schedule. Schedules
are reusable across `L`/`eps` sweeps because they depend only on the
edge endpoints and order.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs

#: Slots per segment — the row width of ``WaveSchedule.slots`` and the
#: trip unit of every vectorized consumer. Waves are padded only up to
#: the next multiple of ``SEG`` (not to a global max), so per-wave
#: padding is < SEG slots. 8 matches the TPU sublane granularity the
#: old ``WIDTH_ALIGN`` targeted.
SEG = 8

#: Back-compat alias (schedule widths are multiples of this).
WIDTH_ALIGN = SEG

@dataclasses.dataclass(frozen=True)
class WaveSchedule:
    """A conflict-free, fill-packed wave decomposition of one edge stream.

    ``wave`` int32 [m]: wave id per stream position (-1 = unscheduled,
    i.e. a padding edge). ``order`` int32 [num_scheduled]: stream
    positions sorted by (wave, stream position) — the wave-major
    permutation. ``offsets`` int32 [num_waves + 1]: CSR offsets of each
    wave inside ``order``. ``slots`` int32 [num_segments, SEG]: the
    packed slot layout — wave k occupies segment rows
    ``seg_offsets[k] : seg_offsets[k + 1]`` back-to-back, -1 in the
    (< SEG) padding slots at its tail. Every row is vertex-disjoint (a
    subset of one wave), which is the only invariant row-major consumers
    need.

    ``schedule_seconds`` / ``pack_seconds`` record the host cost of the
    assignment and layout phases. **Deprecated**: both are views of the
    one telemetry timing path (:func:`repro.obs.stopwatch` spans
    ``wave_schedule.assign`` / ``wave_schedule.pack``) kept populated
    for compatibility — new consumers should pass ``telemetry=`` to
    :func:`wave_schedule` and read the spans or
    ``MatchTelemetry.stage_seconds`` instead.
    """

    wave: np.ndarray
    order: np.ndarray
    offsets: np.ndarray
    slots: np.ndarray
    seg_offsets: np.ndarray
    num_edges: int
    schedule_seconds: float = 0.0
    pack_seconds: float = 0.0

    @property
    def num_waves(self) -> int:
        return int(self.offsets.shape[0]) - 1

    @property
    def num_segments(self) -> int:
        return int(self.slots.shape[0])

    @property
    def width(self) -> int:
        """Slots per segment row (= ``SEG``; kept as the legacy name)."""
        return int(self.slots.shape[1])

    @property
    def num_scheduled(self) -> int:
        return int(self.order.shape[0])

    @property
    def fill(self) -> float:
        """Fraction of slots holding a real edge (1.0 = no padding)."""
        total = self.slots.size
        return self.num_scheduled / total if total else 1.0

    def wave_sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    @property
    def max_wave_size(self) -> int:
        sizes = self.wave_sizes()
        return int(sizes.max()) if sizes.size else 0


#: Vertex id of a link entry that touches no vertex: both endpoints of
#: an invalid edge, the second endpoint of a self-loop, and the padding
#: of the bucket. Vertex ids are non-negative, so it never meets one.
NO_VERTEX = -(2**31)

#: Fewest link entries a links program is built for: streams of up to
#: this many endpoints (the tests' streams among them) share one program.
LINK_FLOOR = 4096


def link_bucket(entries: int) -> int:
    """Link entries of the links program for ``entries`` real ones: the
    least ``c * 2**j >= entries`` with ``8 <= c < 16`` (at most 12.5 %
    padding), and at least :data:`LINK_FLOOR`. The program compiles once
    per bucket, not once per stream length."""
    entries = max(entries, LINK_FLOOR)
    shift = entries.bit_length() - 4
    return -(-entries >> shift) << shift


@functools.partial(jax.jit, static_argnames="size")
def _link_vertices(src, dst, valid, order, size):
    """The link entries of ``size`` ranks, side-major: ``[0, size)`` the
    first endpoints of the ranks' edges, ``[size, 2 * size)`` the second
    (rank r is edge ``order[r]``, or r with no ``order``). An entry that
    touches no vertex, and the bucket's padding, holds
    :data:`NO_VERTEX`. One flat array: a ``[size, 2]`` array would be
    tiled (8, 128) on a TPU, 64 times its size."""
    if valid is None:
        valid = jnp.ones(src.shape, bool)
    if order is not None:
        src, dst, valid = src[order], dst[order], valid[order]
    pad = (0, size - src.shape[0])
    u = jnp.pad(jnp.where(valid, src, NO_VERTEX), pad, constant_values=NO_VERTEX)
    v = jnp.where(valid & (src != dst), dst, NO_VERTEX)
    return jnp.concatenate([u, jnp.pad(v, pad, constant_values=NO_VERTEX)])


@jax.jit
def _links_device(vert):
    """Successor links of the conflict DAG over the ranks of ``vert``.

    Rank r (link entries ``vert[r]`` and ``vert[k + r]`` of the bucket's
    k = ``vert.size // 2`` ranks) conflicts with the previous and next
    rank touching either of its vertices. One sort of the (vertex,
    ``2 * rank + side``) pairs groups the entries by vertex in rank
    order, so neighbours of one vertex are one link; a second sort, on
    the entry index, brings each entry's link back to its place.
    Returns (succ int32 [2k], waiting int32 [k + 1]): ``succ[s * k +
    r]`` is the rank of the next edge at r's endpoint s, or the sentinel
    k where there is none; ``waiting[r]`` is how many earlier edges r
    directly waits on (0, 1 or 2). Ranks that touch no vertex (invalid
    edges, the padding) wait on 2k + 1, as does the sentinel
    ``waiting[k]``: more than it can ever be notified, so none of them
    becomes ready.
    """
    entries = vert.shape[0]
    k = entries // 2
    index = lax.iota(jnp.int32, entries)
    side = index >= k
    by_vertex, key = lax.sort(
        (vert, 2 * jnp.where(side, index - k, index) + side), num_keys=2
    )
    same = (by_vertex[1:] == by_vertex[:-1]) & (by_vertex[1:] != NO_VERTEX)
    # per sorted entry: 2 * (rank of its successor) + (has a predecessor)
    nxt = jnp.append(jnp.where(same, key[1:] >> 1, k), k)
    link = 2 * nxt + jnp.concatenate([jnp.zeros(1, bool), same]).astype(jnp.int32)
    _, link = lax.sort(((key & 1) * k + (key >> 1), link), num_keys=1)
    never = 2 * k + 1
    waiting = jnp.where(
        vert[:k] == NO_VERTEX, never, (link[:k] & 1) + (link[k:] & 1)
    )
    return link >> 1, jnp.append(waiting, never)


def _device_stream(src, dst, valid, order):
    """The stream, and ``order`` where given, as device arrays. Device
    arrays are taken as they are. Host arrays are checked (vertex ids
    must be non-negative int32) and padded with invalid edges to a
    bucket, so that host streams of one bucket share one
    :func:`_link_vertices` program (padded ranks are the invalid
    position ``m``), then put on the default device."""
    if order is not None:
        order = np.asarray(order, np.int32)
    if isinstance(src, jax.Array):
        return src, dst, valid, None if order is None else jnp.asarray(order)
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    m = src.shape[0]
    valid = np.ones(m, bool) if valid is None else np.asarray(valid, bool)
    ids = np.concatenate([src[valid], dst[valid]])
    if ids.size and (ids.min() < 0 or ids.max() >= 2**31):
        raise ValueError(
            f"vertex ids [{ids.min()}, {ids.max()}] are not non-negative int32"
        )
    pad = link_bucket(2 * m + 2) // 2 - m
    src, dst = (np.pad(a.astype(np.int32), (0, pad)) for a in (src, dst))
    valid = np.pad(valid, (0, pad))
    if order is not None:
        k = order.shape[0]
        order = np.pad(order, (0, link_bucket(2 * k) // 2 - k), constant_values=m)
    return (
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
        None if order is None else jnp.asarray(order),
    )


def _conflict_links(src, dst, valid, order, telemetry):
    """:func:`_links_device` over the ranks of ``order`` (stream
    positions where it is None), on the device that holds the stream;
    returns the links as host arrays (``waiting`` writable: the peel
    counts it down)."""
    ranks = src.shape[0] if order is None else order.shape[0]
    size = link_bucket(2 * ranks) // 2
    succ, waiting = _links_device(_link_vertices(src, dst, valid, order, size=size))
    jax.block_until_ready((succ, waiting))
    with telemetry.span("copy.d2h", what="links"):
        succ.copy_to_host_async()
        waiting.copy_to_host_async()
        succ = np.asarray(succ).reshape(2, size)
        waiting = np.array(waiting)
    telemetry.counters.put("schedule.link_entries", 2 * size)
    return succ, waiting


def _peel_waves(succ: np.ndarray, waiting: np.ndarray):
    """Waves of the schedule, by indegree peel of the conflict DAG.

    Pass d resolves exactly the edges of conflict depth d (an edge is
    ready once every earlier edge sharing an endpoint has a depth, and
    its depth is one past its deepest predecessor — so the ready
    frontier of pass d IS wave d). Each edge enters the frontier once
    and notifies at most two successors, so total element work is O(m)
    spread over ``num_waves`` vectorized passes — no per-edge Python
    loop. ``succ`` [2, k] / ``waiting`` [k + 1] are
    :func:`_conflict_links`' output; ``waiting`` is consumed.

    Returns (ranks int64 [k], counts int64 [num_waves]): the frontiers
    concatenated — wave-major, ascending rank inside each wave, the
    order the pack needs — and each wave's size.
    """
    fronts = []
    # a scalar of waiting's own dtype keeps np.subtract.at on its fast
    # path: a Python int sends int32 counts down the generic loop, about
    # 3x slower over a whole peel
    one = waiting.dtype.type(1)
    frontier = np.flatnonzero(waiting == 0)
    while frontier.size:
        fronts.append(frontier)
        nxt = succ.take(frontier, 1).ravel()
        np.subtract.at(waiting, nxt, one)
        frontier = nxt[waiting.take(nxt) == 0]
        if frontier.size > 1:
            # sorts the frontier; a rank occurs twice in ``nxt`` when
            # both of its predecessors resolved this pass
            frontier = np.unique(frontier)
    if not fronts:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    counts = np.fromiter(map(len, fronts), np.int64, len(fronts))
    return np.concatenate(fronts), counts


def wave_schedule(
    src,
    dst,
    valid=None,
    order=None,
    seg: int = SEG,
    telemetry=obs.DISABLED,
) -> WaveSchedule:
    """Decompose a stream into vertex-disjoint, fill-packed waves.

    ``order`` (optional int array [m]) pre-permutes the stream — e.g.
    ``repro.core.blocked.lexicographic_order`` — so the waves respect the
    *processing* order rather than the arrival order; the returned
    schedule still indexes original stream positions. ``valid`` masks
    padding edges, which are left unscheduled (``wave == -1``).

    Every edge is placed at its conflict depth (the wave-count minimal
    assignment), so any two edges sharing a vertex land in distinct
    waves in processing order while independent edges pack together.
    ``seg`` is the slot width of the packed layout (see :data:`SEG`).

    ``src`` / ``dst`` / ``valid`` may be host arrays or device arrays.
    The links are built on the device: device arrays stay where they
    are (vertex ids must be non-negative), host arrays are checked and
    put on the default device.

    ``telemetry`` records the two phases as spans
    (``wave_schedule.assign`` / ``wave_schedule.pack``; the
    assignment's children ``wave_schedule.links``, which holds the
    device program and its ``copy.d2h`` of the links, and
    ``wave_schedule.peel``) plus the schedule geometry counters and
    ``schedule.link_entries`` (the link entries the device sorted,
    padding included); the deprecated ``schedule_seconds`` /
    ``pack_seconds`` fields are populated from the *same* stopwatch
    measurements, so there is one timing path either way. The casts on
    either side of the two phases are the spans ``wave_schedule.prepare``
    (host streams put on the device) and ``wave_schedule.emit`` (the
    int32 arrays of the schedule); neither is part of a phase's seconds.
    """
    if seg < 1:
        raise ValueError(f"seg must be >= 1, got {seg}")
    m = len(src)
    if len(dst) != m:
        raise ValueError(f"src/dst length mismatch: {m} vs {len(dst)}")
    with telemetry.span("wave_schedule.prepare"):
        if order is not None:
            order = np.asarray(order, dtype=np.int64)
        src, dst, valid, dev_order = _device_stream(src, dst, valid, order)

    with obs.stopwatch(telemetry, "wave_schedule.assign") as sw_assign:
        # ranks are stream positions, or indices into ``order``
        with telemetry.span("wave_schedule.links"):
            succ, waiting = _conflict_links(src, dst, valid, dev_order, telemetry)
        with telemetry.span("wave_schedule.peel"):
            ranks, counts = _peel_waves(succ, waiting)

    with obs.stopwatch(telemetry, "wave_schedule.pack") as sw_pack:
        num_waves = counts.shape[0]
        offsets = np.zeros(num_waves + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        wave_ids = np.repeat(np.arange(num_waves, dtype=np.int64), counts)
        # wave-major, stream-position-minor: ranks are ascending inside
        # each wave, and so are their stream positions unless an
        # explicit ``order`` permuted them — then one sort of the packed
        # (wave, position) key restores position order inside each wave
        order_out = ranks
        if order is not None:
            pb = m.bit_length()
            order_out = np.sort((wave_ids << pb) | order[ranks]) & ((1 << pb) - 1)
        wave = np.full(m, -1, dtype=np.int64)
        wave[order_out] = wave_ids

        # fill-packed layout: wave k occupies ceil(counts[k] / seg) segment
        # rows back-to-back; only its last row carries (< seg) padding
        seg_counts = -(-counts // seg)
        seg_offsets = np.zeros(num_waves + 1, dtype=np.int64)
        np.cumsum(seg_counts, out=seg_offsets[1:])
        num_segments = int(seg_offsets[-1])
        slots = np.full((num_segments, seg), -1, dtype=np.int64)
        if num_segments:
            within = np.arange(len(order_out)) - np.repeat(offsets[:-1], counts)
            row = np.repeat(seg_offsets[:-1], counts) + within // seg
            slots[row, within % seg] = order_out

    with telemetry.span("wave_schedule.emit"):
        schedule = WaveSchedule(
            wave=wave.astype(np.int32),
            order=order_out.astype(np.int32),
            offsets=offsets.astype(np.int32),
            slots=slots.astype(np.int32),
            seg_offsets=seg_offsets.astype(np.int32),
            num_edges=m,
            schedule_seconds=sw_assign.seconds,
            pack_seconds=sw_pack.seconds,
        )
    if telemetry.enabled:
        telemetry.counters.update(schedule_counters(schedule))
    return schedule


def schedule_counters(schedule: WaveSchedule) -> dict:
    """The schedule-geometry counter set (``schedule.*``).

    Bit-exact copies of the schedule's own accounting — the telemetry
    layer's view of what the scheduler already computed (and used to
    throw away). Shared by :func:`wave_schedule` and the engine
    recorders in ``kernels/substream_match/ops.py``.
    """
    return {
        "schedule.num_edges": int(schedule.num_edges),
        "schedule.num_waves": int(schedule.num_waves),
        "schedule.num_segments": int(schedule.num_segments),
        "schedule.seg_width": int(schedule.width),
        "schedule.num_scheduled": int(schedule.num_scheduled),
        "schedule.padding_slots": int(schedule.slots.size - schedule.num_scheduled),
        "schedule.max_wave_size": int(schedule.max_wave_size),
        "schedule.fill": float(schedule.fill),
    }


def layout_counters(layout: "BlockAlignedLayout", schedule: WaveSchedule) -> dict:
    """The block-aligned layout counter set (``layout.*``) — the mega
    path's extra padding accounting on top of :func:`schedule_counters`."""
    live = int((layout.slots >= 0).sum())
    return {
        "layout.num_tiles": int(layout.num_tiles),
        "layout.num_segments": int(layout.num_segments),
        "layout.seg_block": int(layout.seg_block),
        "layout.padding_rows": int(layout.num_segments - schedule.num_segments),
        "layout.padding_slots": int(layout.slots.size - live),
        "layout.fill": float(layout.fill),
    }


def validate_schedule(schedule: WaveSchedule, src, dst, valid=None) -> None:
    """Vectorized safety check that ``schedule`` fits this stream.

    Guards the documented reuse path (precomputed schedules amortized
    across runs) against stale schedules — e.g. one built for a stream
    that was permuted afterwards. A non-disjoint wave would corrupt the
    engines silently (the kernels' row-addressed scatter relies on
    disjointness), so this raises instead. Checks length, that exactly
    the valid edges are scheduled, and per-wave vertex-disjointness —
    all O(m log m) numpy, negligible next to a kernel run. Deliberately
    does NOT pin the conflict order to stream order: schedules built
    over an explicit processing ``order`` are legitimate and simply
    realize the greedy matching of that order.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    m = schedule.num_edges
    if src.shape[0] != m:
        raise ValueError(
            f"wave schedule built for {m} edges, stream has {src.shape[0]}"
        )
    valid_np = np.ones(m, bool) if valid is None else np.asarray(valid, bool)
    if not np.array_equal(schedule.wave >= 0, valid_np):
        raise ValueError(
            "wave schedule does not cover exactly this stream's valid "
            "edges; rebuild the schedule for the current stream"
        )
    order = schedule.order
    # the engines gather from ``slots``, so check it agrees with the
    # wave-major permutation (its non-padding entries ARE ``order``) —
    # a schedule whose derived fields drifted from its slot layout would
    # otherwise pass the wave checks below and still corrupt the gather
    flat = schedule.slots.reshape(-1)
    if not np.array_equal(flat[flat >= 0], order):
        raise ValueError(
            "wave schedule slot layout disagrees with its wave order "
            "(corrupted or hand-built schedule); rebuild it with "
            "wave_schedule on the current stream"
        )
    if order.size == 0:
        return
    # order must be in-range and duplicate-free BEFORE it is used to
    # index the stream: a negative entry would silently wrap through
    # numpy indexing (src[-5] is a real edge) and corrupt the gather
    # with no error — the exact failure mode this check exists to stop
    if order.min() < 0 or order.max() >= m or np.unique(order).size != order.size:
        raise ValueError(
            "wave schedule order is not a permutation of edge indices "
            "(out-of-range or duplicate entries; corrupted or "
            "hand-built schedule); rebuild it with wave_schedule on "
            "the current stream"
        )
    # per-wave disjointness: sort (wave, vertex) pairs over both
    # endpoints (self-loops contribute one), adjacent duplicates are
    # conflicts. Checked over the full wave, not just segment rows —
    # strictly stronger than what the row-major consumers need. The two
    # keys are fused into one int64 (vertex ids fit far below 2**31 and
    # wave ids below m, so wave * (max_vertex + 1) + vertex cannot
    # overflow or collide) — one np.sort instead of a two-pass lexsort,
    # which halves the dominant host cost every engine pays per call on
    # the precomputed-schedule path.
    u = src[order].astype(np.int64)
    v = dst[order].astype(np.int64)
    w_ids = schedule.wave[order].astype(np.int64)
    keep = u != v
    verts = np.concatenate([u, v[keep]])
    waves = np.concatenate([w_ids, w_ids[keep]])
    key = np.sort(waves * (int(verts.max()) + 1) + verts)
    dup = key[1:] == key[:-1]
    if dup.any():
        raise ValueError(
            "wave schedule is not vertex-disjoint for this stream "
            "(stale or built for a different edge order); rebuild it "
            "with wave_schedule on the current stream"
        )


def resolve_schedule(
    src,
    dst,
    valid,
    schedule: WaveSchedule | None = None,
    telemetry=obs.DISABLED,
) -> WaveSchedule:
    """Build a schedule for the stream, or validate a precomputed one.

    The single entry every wave consumer (`mwm_waves`, the Pallas mega
    path, rounds-with-waves) goes through, so the validation rules stay
    in one place. ``telemetry`` records the build (or validation) cost
    as ``wave_schedule.*`` spans.
    """
    if schedule is None:
        return wave_schedule(src, dst, valid=valid, telemetry=telemetry)
    with telemetry.span("wave_schedule.validate"):
        validate_schedule(schedule, src, dst, valid)
    return schedule


@dataclasses.dataclass(frozen=True)
class BlockAlignedLayout:
    """A :class:`WaveSchedule` slot layout re-padded to ``seg_block`` tiles.

    The megakernel (`repro.kernels.substream_match`) consumes the slot
    stream one *tile* — ``seg_block`` consecutive segment rows, i.e.
    ``seg_block * SEG`` slots — per gather/compute/scatter op. A tile op
    is only safe when every slot in the tile is vertex-disjoint, which
    holds exactly when no tile straddles a wave boundary. This layout
    therefore pads each wave's segment-row run up to the next
    ``seg_block`` multiple (padding rows are all ``-1``), so

    * ``slots`` is ``[num_tiles * seg_block, SEG]`` int32; rows
      ``seg_offsets[k] : seg_offsets[k + 1]`` belong to wave ``k`` and
      that range length is a ``seg_block`` multiple;
    * ``seg_offsets`` int32 [num_waves + 1] is monotone, block-aligned
      (every entry a ``seg_block`` multiple), and its last entry is the
      total aligned segment count;
    * every stream position scheduled by the source schedule occupies
      exactly one slot (padding only ever *adds* ``-1`` slots).

    ``fill`` is the real-edge fraction of the aligned layout — always
    ≤ the source schedule's fill; the megakernel trades it for a
    ~``seg_block``× cut in sequential tile trips.
    """

    slots: np.ndarray
    seg_offsets: np.ndarray
    seg_block: int
    num_edges: int

    @property
    def num_tiles(self) -> int:
        return int(self.slots.shape[0]) // self.seg_block

    @property
    def num_segments(self) -> int:
        return int(self.slots.shape[0])

    @property
    def width(self) -> int:
        return int(self.slots.shape[1])

    @property
    def fill(self) -> float:
        total = self.slots.size
        return int((self.slots >= 0).sum()) / total if total else 1.0


def block_aligned_layout(
    schedule: WaveSchedule, seg_block: int
) -> BlockAlignedLayout:
    """Re-pad ``schedule.slots`` so every wave spans whole tiles.

    Pure numpy re-layout (no re-scheduling): wave ``k``'s segment rows
    are copied back-to-back to a ``seg_block``-aligned base row and the
    gap up to the next aligned base is left as ``-1`` padding rows. The
    result is the megakernel's HBM slot stream: consecutive groups of
    ``seg_block`` rows ("tiles") never straddle a wave, so each tile is
    vertex-disjoint and one ``[seg_block * SEG, width]`` tile op per
    trip is bit-identical to the sequential scan.
    """
    if seg_block < 1:
        raise ValueError(f"seg_block must be >= 1, got {seg_block}")
    seg = schedule.width
    segc = np.diff(schedule.seg_offsets).astype(np.int64)
    segc_aligned = -(-segc // seg_block) * seg_block
    offsets = np.zeros(segc_aligned.shape[0] + 1, np.int64)
    np.cumsum(segc_aligned, out=offsets[1:])
    total = int(offsets[-1])
    slots = np.full((total, seg), -1, np.int64)
    if schedule.num_segments:
        src_rows = np.arange(schedule.num_segments, dtype=np.int64)
        wave_of_row = np.repeat(
            np.arange(schedule.num_waves, dtype=np.int64), segc
        )
        dst_rows = offsets[wave_of_row] + (
            src_rows - schedule.seg_offsets[wave_of_row]
        )
        slots[dst_rows] = schedule.slots
    return BlockAlignedLayout(
        slots=slots.astype(np.int32),
        seg_offsets=offsets.astype(np.int32),
        seg_block=seg_block,
        num_edges=schedule.num_edges,
    )


def check_block_aligned(layout: BlockAlignedLayout, schedule: WaveSchedule) -> None:
    """Assert the block-aligned invariants (host-side, used by tests).

    * offsets are monotone, ``seg_block``-aligned, and end at the total;
    * every slot of the source schedule is covered exactly once, in the
      same wave-major order (the non-padding entries ARE ``order``);
    * padding rows appear only at the tail of each wave's tile run, so
      no tile straddles a wave boundary — the invariant that makes one
      tile op per trip race-free.
    """
    offs = layout.seg_offsets
    sb = layout.seg_block
    assert offs[0] == 0 and offs[-1] == layout.num_segments
    assert (np.diff(offs) >= 0).all(), "offsets must be monotone"
    assert (offs % sb == 0).all(), "offsets must be seg_block-aligned"
    flat = layout.slots.reshape(-1)
    live = flat[flat >= 0]
    assert np.array_equal(live, schedule.order), "slot coverage/order"
    counts = np.bincount(live, minlength=schedule.num_edges)
    assert counts.max(initial=0) <= 1, "a stream position occupies two slots"
    for k in range(schedule.num_waves):
        rows = layout.slots[offs[k] : offs[k + 1]]
        members = schedule.order[schedule.offsets[k] : schedule.offsets[k + 1]]
        rflat = rows.reshape(-1)
        assert (rflat[: len(members)] == members).all(), f"wave {k} layout"
        assert (rflat[len(members) :] == -1).all(), f"wave {k} padding"


def scatter_slot_assignments(slots, vals, m: int):
    """Scatter per-slot kernel outputs back to stream positions.

    ``slots`` int [..., W] maps slots to stream positions (-1 = padding),
    ``vals`` the matching per-slot assigned indices (>= -1). Returns
    int32 [m] with -1 for unscheduled edges. Padding slots alias position
    0 with value -1, so the max-scatter makes them exact no-ops. Safe
    inside jit (pure jnp).
    """
    import jax.numpy as jnp

    flat = slots.reshape(-1)
    vals = vals.reshape(-1)[: flat.shape[0]]
    live = flat >= 0
    return (
        jnp.full((m,), -1, jnp.int32)
        .at[jnp.where(live, flat, 0)]
        .max(jnp.where(live, vals, -1))
    )


def slot_arrays(schedule: WaveSchedule, src, dst, weight, valid=None):
    """Gather per-slot endpoint/weight arrays for vectorized consumers.

    Returns numpy ``(u, v, w, ok)``, each shaped [num_segments, SEG].
    Padding slots get ``u == v == 0`` and ``w == 0`` — below every
    substream threshold and a self-loop besides, so they can never match
    (the XLA wave engine relies on this encoding).
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    weight = np.asarray(weight)
    slots = schedule.slots
    ok = slots >= 0
    if valid is not None:
        ok = ok & np.where(slots >= 0, np.asarray(valid, bool)[np.maximum(slots, 0)], False)
    safe = np.maximum(slots, 0)
    u = np.where(ok, src[safe], 0).astype(np.int32)
    v = np.where(ok, dst[safe], 0).astype(np.int32)
    w = np.where(ok, weight[safe], 0).astype(np.float32)
    return u, v, w, ok


def greedy_depths(src, dst, valid=None, order=None) -> np.ndarray:
    """Reference conflict depths (0-based), sequential oracle.

    ``depth[e] = 1 + max(depth of previous edge at u, at v)`` walked in
    processing order — the per-edge loop the vectorized scheduler
    replaced, kept as the test oracle for the "every edge is placed at
    its conflict depth" invariant. Returns int64 [m], -1 for
    unscheduled (invalid) edges.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    m = src.shape[0]
    valid_np = np.ones(m, bool) if valid is None else np.asarray(valid, bool)
    positions = np.arange(m) if order is None else np.asarray(order, dtype=np.int64)
    n_hint = int(max(src.max(), dst.max())) + 1 if m else 1
    last = np.full(n_hint, -1, np.int64)
    depth = np.full(m, -1, np.int64)
    for e in positions.tolist():
        if not valid_np[e]:
            continue
        u, v = src[e], dst[e]
        d = 1 + max(last[u], last[v])
        depth[e] = d
        last[u] = d
        last[v] = d
    return depth


def check_schedule(schedule: WaveSchedule, src, dst, valid=None, order=None) -> None:
    """Assert the wave invariants (used by tests; cheap, host-side).

    * every scheduled wave is vertex-disjoint (self-loops use one slot);
    * conflicting edges appear in processing order across waves
      (``order`` is the explicit permutation the schedule was built
      with, if any — stream order otherwise);
    * every edge sits exactly at its conflict depth;
    * ``order``/``offsets``/``seg_offsets``/``slots`` describe the same
      fill-packed decomposition: wave k's members fill its segment rows
      back-to-back with padding only at the tail of its last row.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    wave = schedule.wave
    if valid is not None:
        valid = np.asarray(valid, bool)
        assert (wave[~valid] == -1).all(), "padding edges must be unscheduled"
        assert (wave[valid] >= 0).all(), "valid edges must be scheduled"
    seg = schedule.width
    for k in range(schedule.num_waves):
        members = schedule.order[schedule.offsets[k] : schedule.offsets[k + 1]]
        assert (wave[members] == k).all()
        verts = []
        for e in members.tolist():
            verts.append(src[e])
            if dst[e] != src[e]:
                verts.append(dst[e])
        assert len(verts) == len(set(verts)), f"wave {k} not vertex-disjoint"
        rows = schedule.slots[schedule.seg_offsets[k] : schedule.seg_offsets[k + 1]]
        flat = rows.reshape(-1)
        assert rows.shape[0] == -(-len(members) // seg), f"wave {k} segment count"
        assert (flat[: len(members)] == members).all(), f"wave {k} slot layout"
        assert (flat[len(members) :] == -1).all(), f"wave {k} slot padding"
    depths = greedy_depths(src, dst, valid=valid, order=order)
    scheduled = wave >= 0
    assert (wave[scheduled] == depths[scheduled]).all(), "edge off its depth"
    # order preservation among conflicting edges (in processing order)
    positions = (
        np.nonzero(scheduled)[0]
        if order is None
        else np.asarray(order)[wave[np.asarray(order)] >= 0]
    )
    touch: dict[int, int] = {}
    for e in positions.tolist():
        for x in {int(src[e]), int(dst[e])}:
            if x in touch:
                assert wave[touch[x]] < wave[e], (
                    f"edges {touch[x]} and {e} share vertex {x} but waves "
                    f"{wave[touch[x]]} >= {wave[e]}"
                )
            touch[x] = e

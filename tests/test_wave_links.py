"""The wave schedule's conflict links, built on the device.

The oracle is the host scheduler the device links replaced: one
``np.sort`` of packed int64 endpoint keys over the valid ranks, an
indegree peel, and the wave-major pack. The links are uniquely defined
by the stream, so every array of the schedule must come out the same,
whether the stream arrives as host arrays or already on the device.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.graph import waves
from repro.graph.waves import SEG, link_bucket, wave_schedule


def _oracle_links(su, sv):
    """(succ int64 [k, 2], waiting int64 [k + 1]) over ranks 0..k-1 from
    one sort of packed ``((vertex << rb) | rank) << 1 | side`` keys."""
    k = su.shape[0]
    shift = k.bit_length() + 1
    other = np.flatnonzero(su != sv)
    key = np.concatenate([
        (su << shift) | np.arange(0, 2 * k, 2),
        (sv[other] << shift) | (2 * other + 1),
    ])
    key.sort()
    vert = key >> shift
    same = vert[1:] == vert[:-1]
    key &= (1 << shift) - 1
    nxt = key[1:][same] >> 1
    succ = np.full(2 * k, k, np.int64)
    succ[key[:-1][same]] = nxt
    waiting = np.bincount(nxt, minlength=k + 1)
    waiting[k] = 2 * k + 1
    return succ.reshape(k, 2), waiting


def _oracle_peel(succ, waiting):
    fronts = []
    frontier = np.flatnonzero(waiting == 0)
    while frontier.size:
        fronts.append(frontier)
        nxt = succ.take(frontier, 0).ravel()
        np.subtract.at(waiting, nxt, 1)
        frontier = np.unique(nxt[waiting.take(nxt) == 0])
    if not fronts:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(fronts), np.array([len(f) for f in fronts], np.int64)


def _oracle_schedule(src, dst, valid=None, order=None, seg=SEG):
    """The schedule's arrays (int32) as the host scheduler built them."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    m = src.shape[0]
    valid = np.ones(m, bool) if valid is None else np.asarray(valid, bool)
    positions = np.arange(m) if order is None else np.asarray(order, np.int64)
    positions = positions[valid[positions]]
    ranks, counts = _oracle_peel(*_oracle_links(src[positions], dst[positions]))
    num_waves = counts.shape[0]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    wave_ids = np.repeat(np.arange(num_waves), counts)
    # wave-major, stream position inside each wave
    order_out = positions[ranks][np.lexsort((positions[ranks], wave_ids))]
    wave = np.full(m, -1, np.int64)
    wave[order_out] = wave_ids
    seg_offsets = np.concatenate([[0], np.cumsum(-(-counts // seg))])
    slots = np.full((int(seg_offsets[-1]), seg), -1, np.int64)
    for k in range(num_waves):
        rows = slots[seg_offsets[k] : seg_offsets[k + 1]].reshape(-1)
        rows[: counts[k]] = order_out[offsets[k] : offsets[k + 1]]
        slots[seg_offsets[k] : seg_offsets[k + 1]] = rows.reshape(-1, seg)
    arrays = dict(
        wave=wave, order=order_out, offsets=offsets, slots=slots,
        seg_offsets=seg_offsets,
    )
    return {k: np.asarray(a, np.int64).astype(np.int32) for k, a in arrays.items()}


def _case(name):
    """(src, dst, valid, order) of one named stream."""
    rng = np.random.default_rng(sum(map(ord, name)))
    m = 500
    src = rng.integers(0, 60, m)
    dst = rng.integers(0, 60, m)
    if name == "self_loops":
        dst[::5] = src[::5]
        return src, dst, None, None
    if name == "interior_invalid":
        valid = rng.random(m) > 0.3
        valid[-1] = True
        return src, dst, valid, None
    if name == "trailing_padding":
        valid = np.arange(m) < 380
        return src, dst, valid, None
    if name == "duplicate_pairs":
        src[1::4] = src[0]
        dst[1::4] = dst[0]
        return src, dst, None, None
    if name == "hub_star":  # one wave per edge
        return np.zeros(m, np.int64), rng.permutation(np.arange(1, m + 1)), None, None
    if name == "conflict_free":
        return np.arange(0, 2 * m, 2), np.arange(1, 2 * m, 2), None, None
    if name == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), None, None
    if name == "single_edge":
        return np.array([3]), np.array([8]), None, None
    if name == "explicit_order":
        dst[::7] = src[::7]
        return src, dst, rng.random(m) > 0.2, rng.permutation(m)
    raise KeyError(name)


CASES = [
    "self_loops", "interior_invalid", "trailing_padding", "duplicate_pairs",
    "hub_star", "conflict_free", "empty", "single_edge", "explicit_order",
]


def _on_device(src, dst, valid):
    m = len(src)
    return (
        jnp.asarray(np.asarray(src, np.int32)),
        jnp.asarray(np.asarray(dst, np.int32)),
        jnp.asarray(np.ones(m, bool) if valid is None else valid),
    )


@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("name", CASES)
def test_schedule_equals_the_host_links_oracle(name, where):
    src, dst, valid, order = _case(name)
    want = _oracle_schedule(src, dst, valid=valid, order=order)
    if where == "device":
        src, dst, valid = _on_device(src, dst, valid)
    sch = wave_schedule(src, dst, valid=valid, order=order)
    assert sch.num_edges == len(src)
    for field, expected in want.items():
        got = getattr(sch, field)
        assert got.dtype == np.int32, field
        np.testing.assert_array_equal(got, expected, err_msg=field)
    if name == "hub_star":
        assert sch.num_waves == len(src)


@pytest.mark.parametrize("entries", [0, 1, 4096, 4097, 9000, 2**20 + 1, 88_700_800])
def test_link_bucket_pads_at_most_an_eighth(entries):
    size = link_bucket(entries)
    assert size >= max(entries, waves.LINK_FLOOR)
    shift = size.bit_length() - 4
    assert 8 <= size >> shift < 16 and size % (1 << shift) == 0
    if entries > waves.LINK_FLOOR:
        assert size <= entries * 9 / 8


@pytest.mark.parametrize("where", ["host", "device"])
def test_two_lengths_of_one_bucket_share_the_links_program(where):
    m1, m2 = 2830, 2900
    assert link_bucket(2 * m1) == link_bucket(2 * m2 + 2)
    rng = np.random.default_rng(7)
    sizes = []
    for m in (m1, m2):
        src, dst, valid = rng.integers(0, 90, m), rng.integers(0, 90, m), None
        if where == "device":
            src, dst, valid = _on_device(src, dst, valid)
        sch = wave_schedule(src, dst, valid=valid)
        np.testing.assert_array_equal(
            sch.order, _oracle_schedule(np.asarray(src), np.asarray(dst))["order"]
        )
        sizes.append(waves._links_device._cache_size())
    assert sizes[1] == sizes[0]


def test_link_entries_counter_is_recorded():
    rng = np.random.default_rng(1)
    src, dst = rng.integers(0, 50, 3000), rng.integers(0, 50, 3000)
    tel = obs.Telemetry()
    sch = wave_schedule(src, dst, telemetry=tel)
    entries = tel.counters.get("schedule.link_entries")
    assert entries == link_bucket(2 * 3000 + 2) >= 2 * sch.num_edges


def test_host_ids_must_be_non_negative_int32():
    with pytest.raises(ValueError, match="non-negative int32"):
        wave_schedule(np.array([0, -3]), np.array([1, 2]))
    with pytest.raises(ValueError, match="non-negative int32"):
        wave_schedule(np.array([0, 2**31]), np.array([1, 2]))
    # an invalid edge's ids are never read
    sch = wave_schedule(np.array([0, -3]), np.array([1, 2]), valid=[True, False])
    assert sch.wave.tolist() == [0, -1]

"""Plain reference of the substream-centric matching, and the comparison
that decides a run's ``correct``.

Written from the paper's Listing 1 (arXiv:2010.14684) and imports
nothing of the program. Part 1: substream ``i`` admits an edge whose
weight is at least ``(1+eps)**i`` (thresholds computed in float64 and
rounded once to float32, the precision the configuration states); one
pass over the edges in processing order adds an edge to every admitting
substream in which both endpoints are still free, and records it under
the highest such substream. Part 2: the recorded edges, in descending
substream and then ascending stream position, are merged greedily into
one matching. The per-vertex state is an ``L``-bit integer, stored as
``ceil(L/8)`` little-endian bytes (bit ``j`` of byte ``k`` is substream
``8k + j``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Answer:
    """What one job hands back, or what the reference says it should.

    ``assigned`` and ``state`` are ``None`` where the entry does not
    return Part 1."""

    merged: np.ndarray  # int64, sorted stream positions of the matching
    weight: float
    assigned: np.ndarray | None = None  # int32 [m], -1 = recorded nowhere
    state: np.ndarray | None = None  # uint8 [n, ceil(L/8)]


def thresholds(L: int, eps: float, dtype=np.float32) -> np.ndarray:
    return ((1.0 + eps) ** np.arange(L, dtype=np.float64)).astype(dtype)


def admit_counts(src, dst, weight, L: int, eps: float, dtype=np.float32):
    """How many substreams admit each edge (0 for a self-loop): the
    number of thresholds at or below its weight, both in ``dtype``."""
    thr = thresholds(L, eps, dtype).astype(np.float64)
    w = np.asarray(weight).astype(dtype).astype(np.float64)
    cnt = np.searchsorted(thr, w, side="right")
    cnt[np.asarray(src) == np.asarray(dst)] = 0
    return cnt


def blocked_order(src, dst, K: int) -> np.ndarray:
    """Listing 2's blocked order: edges sorted by ``(src // K, dst,
    src)``, ties in stream order."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    return np.lexsort((np.arange(src.size), src, dst, src // K))


def part1(src, dst, cnt, n: int, L: int, order=None):
    """Greedy substream matchings over ``order`` (default: stream
    order). Returns ``(assigned int32 [m], state uint8 [n, ceil(L/8)])``
    with ``assigned`` in stream positions."""
    if L > 64:
        raise ValueError(f"the reference keeps L <= 64 bits per vertex, got {L}")
    m = len(src)
    order = np.arange(m) if order is None else np.asarray(order)
    masks = [(1 << c) - 1 for c in range(L + 1)]
    bits = [0] * n
    assigned = np.full(m, -1, np.int32)
    hit_pos, hit_sub = [], []
    for e, u, v, c in zip(
        order.tolist(),
        np.asarray(src)[order].tolist(),
        np.asarray(dst)[order].tolist(),
        np.asarray(cnt)[order].tolist(),
    ):
        add = masks[c] & ~(bits[u] | bits[v])
        if add:
            bits[u] |= add
            bits[v] |= add
            hit_pos.append(e)
            hit_sub.append(add.bit_length() - 1)
    assigned[np.asarray(hit_pos, np.int64)] = np.asarray(hit_sub, np.int32)
    words = -(-L // 8)
    state = np.asarray(bits, np.uint64).astype("<u8").view(np.uint8)
    return assigned, state.reshape(n, 8)[:, :words].copy()


def part2(src, dst, assigned, n: int) -> np.ndarray:
    """Greedy merge in descending substream, then stream position."""
    assigned = np.asarray(assigned)
    recorded = np.nonzero(assigned >= 0)[0]
    order = recorded[np.lexsort((recorded, -assigned[recorded]))]
    taken = bytearray(n)
    out = []
    for e, u, v in zip(
        order.tolist(), np.asarray(src)[order].tolist(), np.asarray(dst)[order].tolist()
    ):
        if not taken[u] and not taken[v]:
            taken[u] = taken[v] = 1
            out.append(e)
    return np.sort(np.asarray(out, np.int64))


def solve(src, dst, weight, n: int, L: int, eps: float, order=None,
          dtype=np.float32) -> Answer:
    """The full reference answer. ``dtype`` is the precision of the
    admission comparison: float32 as configured; the control passes a
    lower one."""
    cnt = admit_counts(src, dst, weight, L, eps, dtype)
    assigned, state = part1(src, dst, cnt, n, L, order)
    merged = part2(src, dst, assigned, n)
    return Answer(
        merged=merged,
        weight=float(np.asarray(weight, np.float32)[merged].sum()),
        assigned=assigned,
        state=state,
    )


def compare(got: Answer, want: Answer) -> dict:
    """The numbers compared, each 0 for an answer equal to the
    reference: positions whose recorded substream differs, vertices
    whose final state differs, edges in one merged matching and not the
    other, and the relative gap of the matching weights. Part 1 numbers
    appear only where the entry returns Part 1."""
    out = {}
    if got.assigned is not None:
        a = np.asarray(got.assigned)
        out["assigned_diff"] = (
            int(np.count_nonzero(a != want.assigned))
            if a.shape == want.assigned.shape else int(want.assigned.size)
        )
    if got.state is not None:
        s = np.asarray(got.state)
        out["state_diff"] = (
            int(np.count_nonzero((s != want.state).any(axis=1)))
            if s.shape == want.state.shape else int(want.state.shape[0])
        )
    got_set = np.asarray(got.merged, np.int64)
    out["merged_diff"] = int(np.setxor1d(got_set, want.merged).size)
    out["weight_gap"] = abs(float(got.weight) - want.weight) / max(abs(want.weight), 1e-30)
    return out

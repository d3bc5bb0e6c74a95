"""Part 2 (post processing) — greedy merge of the L matchings into the MWM.

The paper runs this on the CPU (<1 % of time, little parallelism). We keep
the faithful host version (numpy) and additionally offer a device version
built on the same greedy-priority machinery as Part 1: merging in
"descending i, then stream order" is itself a greedy maximal matching under
the total priority order ``(L-1-i, position)``, so `mwm_scan` can run it.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.types import EdgeStream, MatchingResult, SubstreamConfig
from repro.core import matching as _matching


def merge_host(
    stream: EdgeStream, result: MatchingResult, cfg: SubstreamConfig,
    telemetry=obs.DISABLED,
) -> np.ndarray:
    """Faithful Listing 1 Part 2. Returns indices (into the stream) of T.

    Consumes only ``result.assigned`` — Part 2 never reads the matching
    bits, so packed-storage results merge without ever unpacking ``mb``.

    The merge order "descending substream i, then stream position" is
    realized with ONE stable argsort over the recorded edges (key
    ``L-1-i`` in the smallest dtype that holds it, ``uint8`` for every
    ``L <= 256``, so numpy takes its radix sort; stability supplies the
    stream-position minor key), and the sorted keys split that order
    into one group per substream.

    The greedy pass decides a whole group at once. An edge is recorded
    under ``i`` only when it set bit ``i`` on both endpoints, and a bit
    once set is never set again, so the edges of one substream form a
    matching: vertex-disjoint. Inside a group no edge's decision then
    depends on another's, only on the vertices taken by higher
    substreams, so greedy over the merge order equals one gather, mask
    and scatter per group, from ``i = L-1`` down to 0 — bit-identical
    to the paper's sequential post-processor. Each group is checked to
    be disjoint (every endpoint reads back its own stamp, and no
    self-loop) in O(group); a group that fails, which no correct Part 1
    produces, is decided edge by edge in stream order, so the result
    equals the sequential loop for every ``assigned``.

    ``telemetry`` records one ``merge.host`` span (the copies to the
    host inside it as ``copy.d2h`` spans, the order and group split as
    ``merge.order`` and the per-group greedy pass as ``merge.greedy``)
    plus the recorded / matched edge counters, the groups decided
    (``merge.greedy_groups``) and the edges the per-edge fallback
    decided (``merge.loop_edges``, 0 on every correct result).
    """
    with telemetry.span("merge.host"):
        with telemetry.span("copy.d2h", what="stream"):
            src = np.asarray(stream.src)
            dst = np.asarray(stream.dst)
        with telemetry.span("copy.d2h", what="assigned"):
            assigned = np.asarray(result.assigned)
        with telemetry.span("merge.order"):
            recorded = np.flatnonzero(assigned >= 0)
            order = recorded
            bounds = np.zeros(1, np.int64)
            if recorded.size:
                key = cfg.L - 1 - assigned[recorded]
                # uint8 for any L <= 256; a substream beyond L-1 (a
                # corrupted result) keys below 0 and widens the dtype
                key = key.astype(np.result_type(
                    np.min_scalar_type(key.min()), np.min_scalar_type(key.max())))
                # descending i, stream order within i: stable sort on the
                # major key alone (``recorded`` is ascending in stream position)
                perm = np.argsort(key, kind="stable")
                order = recorded[perm]
                key = key[perm]
                bounds = np.concatenate(
                    ([0], np.flatnonzero(key[1:] != key[:-1]) + 1, [key.size]))
        if recorded.size == 0:
            # empty / all-dropped streams: a well-formed empty T, skipping
            # the n-sized allocations (n may be 0 here)
            merged = np.zeros(0, dtype=np.int64)
            loop_edges = 0
        else:
            with telemetry.span("merge.greedy"):
                merged, loop_edges = _greedy_groups(src, dst, order, bounds, cfg.n)
    if telemetry.enabled:
        telemetry.counters.add("merge.host.calls")
        telemetry.counters.put("merge.recorded_edges", int(recorded.size))
        telemetry.counters.put("merge.matched_edges", int(merged.size))
        telemetry.counters.put("merge.greedy_groups", int(bounds.size - 1))
        telemetry.counters.put("merge.loop_edges", int(loop_edges))
    return merged


def _greedy_groups(src, dst, order, bounds, n):
    """Greedy over ``order`` split at ``bounds`` into substream groups:
    returns (sorted positions of T, edges decided one at a time)."""
    # intp endpoints: numpy's fancy indexing converts any other dtype on
    # every use
    u, v = src[order].astype(np.intp), dst[order].astype(np.intp)
    taken = np.zeros(n, dtype=bool)
    stamp = np.empty(n, dtype=np.int32)
    slot = np.arange(order.size, dtype=np.int32)
    keep = np.zeros(order.size, dtype=bool)
    loop_edges = 0
    for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        gu, gv, gs = u[a:b], v[a:b], slot[a:b]
        stamp[gu] = gs
        stamp[gv] = gs
        if (stamp[gu] == gs).all() and (stamp[gv] == gs).all() and (gu != gv).all():
            ok = ~taken[gu] & ~taken[gv]
            keep[a:b] = ok
            taken[gu[ok]] = True
            taken[gv[ok]] = True
            continue
        loop_edges += b - a
        for j in range(a, b):
            x, y = u[j], v[j]
            if not taken[x] and not taken[y]:
                taken[x] = True
                taken[y] = True
                keep[j] = True
    return np.sort(order[keep]).astype(np.int64, copy=False), loop_edges


def merge_device(
    stream: EdgeStream, result: MatchingResult, cfg: SubstreamConfig,
    telemetry=obs.DISABLED,
) -> jax.Array:
    """Device-side merge: bool [m] membership mask of T (beyond-paper).

    Re-orders the recorded edges by (descending i, stream position) and runs
    the same one-substream greedy scan. Bit-identical to `merge_host`.
    Like `merge_host`, reads only ``result.assigned`` (packed-safe).
    ``telemetry`` records one ``merge.device`` span.
    """
    with telemetry.span("merge.device"):
        m = stream.num_edges
        assigned = result.assigned
        recorded = assigned >= 0
        # priority: (L-1-i) major, stream position minor — a *stable* argsort on
        # the major key alone keeps stream order inside each substream list.
        major = jnp.where(recorded, cfg.L - 1 - assigned, cfg.L)
        order = jnp.argsort(major, stable=True)
        perm = EdgeStream(
            src=stream.src[order],
            dst=stream.dst[order],
            weight=jnp.ones((m,), jnp.float32),  # single substream, all eligible
            valid=recorded[order],
        )
        one = SubstreamConfig(n=cfg.n, L=1, eps=cfg.eps)
        res = _matching.mwm_scan(perm, one)
        in_t_perm = res.assigned >= 0
        # scatter back to stream order
        mask = jnp.zeros((m,), bool).at[order].set(in_t_perm)
        if telemetry.enabled:
            jax.block_until_ready(mask)
    if telemetry.enabled:
        telemetry.counters.add("merge.device.calls")
    return mask


def matching_weight(
    stream: EdgeStream, edge_idx: np.ndarray, telemetry=obs.DISABLED
) -> float:
    """Summed weight of the stream edges ``edge_idx``; ``telemetry``
    records the weights' copy to the host as a ``copy.d2h`` span."""
    # the int64 cast keeps empty python lists indexable (np.asarray([])
    # is float64, which cannot index)
    idx = np.asarray(edge_idx, dtype=np.int64)
    if idx.size == 0:
        return 0.0
    with telemetry.span("copy.d2h", what="weight"):
        w = np.asarray(stream.weight)
    return float(w[idx].sum())

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
    ``L-1-i``; stability supplies the stream-position minor key), then a
    single greedy pass over those edges only — O(R log R + R) for R
    recorded edges instead of the old O(L·m) scan of the whole stream
    per substream. The greedy pass itself is the dependency chain and
    stays a loop, exactly like the paper's sequential post-processor.

    ``telemetry`` records one ``merge.host`` span (the copies to the
    host inside it as ``copy.d2h`` spans, the sort as ``merge.order``
    and the greedy pass as ``merge.greedy``) plus the recorded / matched
    edge counters.
    """
    with telemetry.span("merge.host"):
        with telemetry.span("copy.d2h", what="stream"):
            src = np.asarray(stream.src)
            dst = np.asarray(stream.dst)
        with telemetry.span("copy.d2h", what="assigned"):
            assigned = np.asarray(result.assigned)
        with telemetry.span("merge.order"):
            recorded = np.nonzero(assigned >= 0)[0]
            # descending i, stream order within i: stable sort on the
            # major key alone (``recorded`` is ascending in stream position)
            order = recorded[np.argsort(cfg.L - 1 - assigned[recorded], kind="stable")]
        if recorded.size == 0:
            # empty / all-dropped streams: a well-formed empty T, skipping
            # the n-sized tbits allocation (n may be 0 here)
            if telemetry.enabled:
                telemetry.counters.add("merge.host.calls")
                telemetry.counters.put("merge.recorded_edges", 0)
                telemetry.counters.put("merge.matched_edges", 0)
            return np.zeros(0, dtype=np.int64)
        with telemetry.span("merge.greedy"):
            tbits = np.zeros(cfg.n, dtype=bool)
            out = []
            for e in order.tolist():
                u, v = src[e], dst[e]
                if not tbits[u] and not tbits[v]:
                    tbits[u] = True
                    tbits[v] = True
                    out.append(e)
            merged = np.sort(np.asarray(out, dtype=np.int64))
    if telemetry.enabled:
        telemetry.counters.add("merge.host.calls")
        telemetry.counters.put("merge.recorded_edges", int(recorded.size))
        telemetry.counters.put("merge.matched_edges", int(merged.size))
    return merged


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

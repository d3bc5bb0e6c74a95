"""Faithful substream-centric MWM — Listing 1 of the paper, in JAX.

Part 1 (stream processing): one pass over the edge stream; for every edge,
all ``L`` substreams are updated *in parallel* (the FPGA's bit-parallel
matching-bit word = our lane-vectorized [L] ops). Part 2 (post
processing): greedy merge in descending substream order (see
:mod:`repro.core.merge`).

This module is the CS-SEQ oracle: every other implementation (blocked /
Pallas / distributed rounds) is tested bit-identical against it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.types import EdgeStream, MatchingResult, SubstreamConfig, eligibility


@partial(jax.jit, static_argnames=("cfg",))
def mwm_scan(
    stream: EdgeStream, cfg: SubstreamConfig, mb0: jax.Array | None = None
) -> MatchingResult:
    """Listing 1, Part 1. Carries MB in a `lax.scan` over the stream.

    ``mb0`` (bool [n, L], default zeros) seeds the matching bits — the
    epoch executor's carried state; chunked runs stay bit-identical to
    one-shot because the greedy update is confluent in the carried MB.

    Per edge e=(u,v,w):
      te    = [w >= (1+eps)^i]_i                (eligibility, Stage 4)
      free  = ~MB[u] & ~MB[v]                   (Stage 5)
      add   = te & free
      MB[u]|= add ; MB[v]|= add                 (Stage 6)
      assigned = highest set bit of add, else -1 (Stage 7; `has_added`
                 collapses to "highest i" because the descending loop in
                 Listing 1 records the first i where the edge is added)
    """
    if cfg.n == 0:
        # scan traces its body even for zero iterations of work per edge,
        # and mb[u] on a zero-row block is an out-of-bounds gather — return
        # the well-formed empty result instead
        return MatchingResult(
            assigned=jnp.full((stream.num_edges,), -1, jnp.int32),
            mb=jnp.zeros((0, cfg.L), dtype=bool),
        )
    thr = cfg.thresholds()

    def step(mb, e):
        u, v, w, ok = e
        u = u.astype(jnp.int32)
        v = v.astype(jnp.int32)
        te = (w >= thr) & ok & (u != v)  # self-loops never match
        mbu = mb[u]
        mbv = mb[v]
        add = te & ~mbu & ~mbv
        mb = mb.at[u].set(mbu | add)
        mb = mb.at[v].set(mbv | add)
        idx = jnp.where(
            add, jax.lax.broadcasted_iota(jnp.int32, add.shape, 0), -1
        ).max()
        return mb, idx

    init = (
        jnp.zeros((cfg.n, cfg.L), dtype=bool)
        if mb0 is None
        else mb0.astype(bool)
    )
    mb, assigned = jax.lax.scan(
        step, init, (stream.src, stream.dst, stream.weight, stream.valid)
    )
    return MatchingResult(assigned=assigned, mb=mb)


@partial(jax.jit, static_argnames=("cfg",))
def substream_matchings(stream: EdgeStream, cfg: SubstreamConfig) -> jax.Array:
    """bool [m, L]: membership of each edge in each substream's matching M_i.

    Note M_i (defined by the matching *bits*) is a superset of the recorded
    list C_i — an edge can be matched in several substreams but recorded in
    one (Listing 1's ``has_added``). Some invariant tests need the full M_i.
    """
    if cfg.n == 0:
        return jnp.zeros((stream.num_edges, cfg.L), dtype=bool)
    thr = cfg.thresholds()

    def step(mb, e):
        u, v, w, ok = e
        u = u.astype(jnp.int32)
        v = v.astype(jnp.int32)
        te = (w >= thr) & ok & (u != v)
        add = te & ~mb[u] & ~mb[v]
        mb = mb.at[u].set(mb[u] | add)
        mb = mb.at[v].set(mb[v] | add)
        return mb, add

    mb0 = jnp.zeros((cfg.n, cfg.L), dtype=bool)
    _, added = jax.lax.scan(
        step, mb0, (stream.src, stream.dst, stream.weight, stream.valid)
    )
    return added


@partial(jax.jit, static_argnames=("cfg", "m"))
def _wave_scan(u, v, w, ok, slots, cfg: SubstreamConfig, m: int, mb0=None):
    """Scan over segments; each step is one vectorized [SEG, L] update.

    ``u/v/w/ok`` are the [num_segments, SEG] fill-packed slot arrays of
    :func:`repro.graph.waves.slot_arrays` — each row is a segment of one
    wave, so it is vertex-disjoint and the per-step work is proportional
    to ``SEG``, not to the largest wave. ``slots`` maps each slot back
    to its stream position (-1 = padding). Returns (assigned [m], mb).
    """
    thr = cfg.thresholds()

    def step(mb, wave):
        wu, wv, ww, wok = wave  # [W] each
        te = (ww[:, None] >= thr[None, :]) & wok[:, None] & (wu != wv)[:, None]
        mbu = mb[wu]  # [W, L]; wave edges are vertex-disjoint, so these
        mbv = mb[wv]  # reads cannot race the scatter below
        add = te & ~mbu & ~mbv
        # scatter-OR (max on bool): padding slots all alias row 0 with
        # add == False, so duplicate indices are no-ops by construction
        mb = mb.at[wu].max(add)
        mb = mb.at[wv].max(add)
        idx = jnp.where(
            add, jax.lax.broadcasted_iota(jnp.int32, add.shape, 1), -1
        ).max(axis=1)
        return mb, idx

    init = (
        jnp.zeros((cfg.n, cfg.L), dtype=bool)
        if mb0 is None
        else mb0.astype(bool)
    )
    mb, idx = jax.lax.scan(step, init, (u, v, w, ok))
    from repro.graph.waves import scatter_slot_assignments

    return scatter_slot_assignments(slots, idx, m), mb


def mwm_waves(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    schedule=None,
    telemetry=obs.DISABLED,
    mb0: jax.Array | None = None,
) -> MatchingResult:
    """Listing 1 Part 1 over conflict-free waves (XLA parity oracle).

    ``mb0`` (bool [n, L], default zeros) seeds the matching bits — the
    epoch executor's carried state.

    Decomposes the stream with :func:`repro.graph.waves.wave_schedule`
    (or reuses a precomputed ``schedule``) and processes one
    vertex-disjoint *segment* (a fill-packed chunk of one wave) per scan
    step — bit-identical to :func:`mwm_scan` in ``assigned`` and ``mb``
    because greedy matching is confluent over vertex-disjoint edges.
    ``#segments`` (≈ m / SEG on well-packed streams) scan steps of
    [SEG, L] vector work replace ``m`` scalar steps.

    Host-side scheduling makes this entry point non-jittable at the top
    level (the wave decomposition is data-dependent); the per-wave scan
    itself is jitted. ``telemetry`` records the same stage split as the
    Pallas engines (engine name ``waves_xla``).
    """
    if cfg.n == 0:
        return MatchingResult(
            assigned=jnp.full((stream.num_edges,), -1, jnp.int32),
            mb=jnp.zeros((0, cfg.L), dtype=bool),
        )
    from repro.graph import waves as _waves

    rec = obs.recorder(
        telemetry, "waves_xla", stream.num_edges, jax.default_backend()
    )
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    if schedule is None:
        schedule = _waves.resolve_schedule(
            src, dst, valid, schedule=None, telemetry=telemetry
        )
        rec.add_stage("schedule", schedule.schedule_seconds)
        rec.add_stage("pack", schedule.pack_seconds)
    else:
        with rec.stage("schedule"):  # precomputed: validation cost only
            schedule = _waves.resolve_schedule(
                src, dst, valid, schedule=schedule, telemetry=telemetry
            )
    with rec.stage("layout"):
        u, v, w, ok = _waves.slot_arrays(
            schedule, src, dst, np.asarray(stream.weight), valid
        )
    if telemetry.enabled:
        rec.put_many(_waves.schedule_counters(schedule))
        rec.put("stream.num_edges", stream.num_edges)
    key = (
        "waves_xla", schedule.num_segments, schedule.width, cfg.n, cfg.L,
        cfg.eps, stream.num_edges, mb0 is not None,
    )
    with rec.device_stage(key):
        assigned, mb = _wave_scan(
            jnp.asarray(u),
            jnp.asarray(v),
            jnp.asarray(w),
            jnp.asarray(ok),
            jnp.asarray(schedule.slots),
            cfg,
            stream.num_edges,
            mb0=None if mb0 is None else jnp.asarray(mb0),
        )
        rec.block((assigned, mb))
    rec.finish()
    return MatchingResult(assigned=assigned, mb=mb)

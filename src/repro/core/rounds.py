"""Deterministic propose–accept parallel matching (beyond-paper scaling path).

Greedy maximal matching w.r.t. a fixed total edge order is unique and equals
the "repeatedly take all locally-minimal live edges" fixed point (parallel
greedy / lexicographically-first matching). We exploit this twice:

 * single device: replaces the sequential O(m) scan by O(#rounds) passes of
   vectorized segment-mins — each pass is pure VPU/MXU-friendly bulk work;
 * multi device: edges shard over the ``data`` axis, substream blocks over
   ``model``; one ``psum``-min per round resolves cross-partition conflicts.

Output is bit-identical to :func:`repro.core.matching.mwm_scan` (tested).
The priority order is the stream position, i.e. exactly Listing 1's order.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import bitpack
from repro.core.types import EdgeStream, MatchingResult, SubstreamConfig

_INF = jnp.iinfo(jnp.int32).max


def _vertex_min(pri_el: jax.Array, src, dst, n: int) -> jax.Array:
    """[n, L] min over live incident-edge priorities (INF where none)."""
    best = jnp.full((n,) + pri_el.shape[1:], _INF, jnp.int32)
    best = best.at[src].min(pri_el)
    best = best.at[dst].min(pri_el)
    return best


def mwm_rounds(
    stream: EdgeStream, cfg: SubstreamConfig, max_rounds: int = 0,
    packed: bool = False, waves=None, telemetry=obs.DISABLED,
) -> MatchingResult:
    """Parallel-rounds equivalent of Listing 1 Part 1 (single device).

    ``packed=True`` ships the final bits in the uint8 bit-plane storage of
    :mod:`repro.core.bitpack` (8x smaller to keep/checkpoint/transfer);
    the round state itself stays bool — the conflict resolution needs
    per-substream scatters, not bitwise words. Unpacking the result is
    bit-identical to the dense output.

    ``waves`` (a :class:`repro.graph.waves.WaveSchedule`) swaps the
    propose–accept fixed point for per-segment updates: instead of
    ``O(#rounds)`` passes that each run a full-[m, L] liveness mask and
    a full-[n, L] ``.at[].min`` vertex reduction, the fill-packed slot
    layout lets each step touch exactly one conflict-free [SEG, L]
    segment — no conflict resolution needed, because a wave *is* the set
    of edges the fixed point would accept given all earlier waves.
    Output is identical either way.

    ``telemetry`` records the call: the wave path delegates to
    :func:`repro.core.matching.mwm_waves` (whose ``waves_xla`` record
    covers the run), the fixed point records one ``rounds`` record whose
    device stage is the whole while-loop dispatch.
    """
    if waves is not None:
        if max_rounds:
            raise ValueError(
                "max_rounds only applies to the propose-accept fixed point; "
                "the wave path always computes the full matching"
            )
        from repro.core import matching as _matching

        res = _matching.mwm_waves(
            stream, cfg, schedule=waves, telemetry=telemetry
        )
        if packed:
            return MatchingResult(
                assigned=res.assigned, mb_packed=bitpack.pack_bits(res.mb),
                L=cfg.L,
            )
        return res
    rec = obs.recorder(
        telemetry, "rounds", stream.num_edges, jax.default_backend()
    )
    if telemetry.enabled:
        rec.put("stream.num_edges", stream.num_edges)
        rec.put("rounds.max_rounds", int(max_rounds))
    key = ("rounds", cfg.n, cfg.L, cfg.eps, max_rounds, packed,
           stream.num_edges)
    with rec.device_stage(key):
        out = _mwm_rounds_fixed_point(stream, cfg, max_rounds, packed)
        rec.block(out)
    rec.finish()
    return out


@partial(jax.jit, static_argnames=("cfg", "max_rounds", "packed"))
def _mwm_rounds_fixed_point(
    stream: EdgeStream, cfg: SubstreamConfig, max_rounds: int = 0,
    packed: bool = False,
) -> MatchingResult:
    thr = cfg.thresholds()
    m = stream.num_edges
    src = stream.src.astype(jnp.int32)
    dst = stream.dst.astype(jnp.int32)
    te = (stream.weight[:, None] >= thr[None, :]) & stream.valid[:, None]
    te &= (src != dst)[:, None]  # self-loops never join a matching
    pri = jnp.arange(m, dtype=jnp.int32)

    def cond(state):
        alive, _, _, it = state
        cap = jnp.int32(max_rounds) if max_rounds else jnp.int32(m + 1)
        return jnp.any(alive) & (it < cap)

    def body(state):
        alive, added, mb, it = state
        pri_el = jnp.where(alive, pri[:, None], _INF)
        best = _vertex_min(pri_el, src, dst, cfg.n)
        win = alive & (best[src] == pri_el) & (best[dst] == pri_el)
        mb = mb.at[src].max(win)
        mb = mb.at[dst].max(win)
        added |= win
        alive &= ~(mb[src] | mb[dst])
        return alive, added, mb, it + 1

    alive0 = te
    added0 = jnp.zeros((m, cfg.L), bool)
    mb0 = jnp.zeros((cfg.n, cfg.L), bool)
    _, added, mb, rounds = jax.lax.while_loop(
        cond, body, (alive0, added0, mb0, jnp.int32(0))
    )
    assigned = jnp.where(
        added, jax.lax.broadcasted_iota(jnp.int32, added.shape, 1), -1
    ).max(axis=1)
    if packed:
        return MatchingResult(
            assigned=assigned, mb_packed=bitpack.pack_bits(mb), L=cfg.L
        )
    return MatchingResult(assigned=assigned, mb=mb)


def mwm_rounds_sharded(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    mesh,
    edge_axis: str = "data",
    substream_axis: str = "model",
):
    """Distributed rounds: edges sharded over ``edge_axis``, substreams over
    ``substream_axis``. Every device holds the full [n, L_local] bit block
    for its substream slice; cross-edge-partition conflicts are resolved by
    one `psum`-min per round. Returns a :class:`MatchingResult` with global
    (replicated-over-edge-axis) ``mb`` and edge-sharded ``assigned``.
    """
    thr_full = cfg.thresholds()

    def local(src, dst, w, valid, thr):
        m_loc = src.shape[0]
        n_edge_shards = jax.lax.axis_size(edge_axis)
        shard_id = jax.lax.axis_index(edge_axis)
        # global stream position = shard_id * m_loc + local position
        pri = (shard_id * m_loc + jnp.arange(m_loc)).astype(jnp.int32)
        te = (w[:, None] >= thr[None, :]) & valid[:, None] & (src != dst)[:, None]
        L_loc = thr.shape[0]

        def cond(state):
            alive, _, _, it = state
            any_alive = jax.lax.psum(jnp.any(alive).astype(jnp.int32), edge_axis)
            return (any_alive > 0) & (it < n_edge_shards * m_loc + 1)

        def body(state):
            alive, added, mb, it = state
            pri_el = jnp.where(alive, pri[:, None], _INF)
            best = _vertex_min(pri_el, src, dst, cfg.n)
            best = jax.lax.pmin(best, edge_axis)
            win = alive & (best[src] == pri_el) & (best[dst] == pri_el)
            mb_new = jnp.zeros_like(mb).at[src].max(win).at[dst].max(win)
            mb = mb | (jax.lax.pmax(mb_new.astype(jnp.int8), edge_axis) > 0)
            added |= win
            alive &= ~(mb[src] | mb[dst])
            return alive, added, mb, it + 1

        alive0 = te
        added0 = jnp.zeros((m_loc, L_loc), bool)
        mb0 = jnp.zeros((cfg.n, L_loc), bool)
        _, added, mb, _ = jax.lax.while_loop(
            cond, body, (alive0, added0, mb0, jnp.int32(0))
        )
        base = jax.lax.axis_index(substream_axis) * L_loc
        assigned = jnp.where(
            added, base + jax.lax.broadcasted_iota(jnp.int32, added.shape, 1), -1
        ).max(axis=1)
        # global max over substream shards: each edge recorded in its highest
        assigned = jax.lax.pmax(assigned, substream_axis)
        return assigned, mb

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(edge_axis),
            P(edge_axis),
            P(edge_axis),
            P(edge_axis),
            P(substream_axis),
        ),
        out_specs=(P(edge_axis), P(None, substream_axis)),
        check_vma=False,
    )
    return fn(stream.src, stream.dst, stream.weight, stream.valid, thr_full)

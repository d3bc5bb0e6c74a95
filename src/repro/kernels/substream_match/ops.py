"""Typed / padded entry point for the substream_match Pallas kernel.

Memory accounting (the §4.3 "storage" analysis, TPU edition)
------------------------------------------------------------
The kernel keeps the matching-bit block resident in VMEM and streams the
slots through SMEM (see :mod:`repro.kernels.substream_match.kernel`).
Mosaic tiles 32-bit data as (8, 128), so the block is int32
``[rows, 128]`` with each vertex's words folded into one lane range of
a row; :func:`vmem_plan` counts it exactly as the compiler allocates it:
rows padded to 8 sublanes, lanes to 128. The block is the only VMEM
buffer the kernel asks for (it is the pallas_call's resident output, so
it is single-buffered), and each kernel passes ``vmem_limit_bytes`` =
block + ``VMEM_HEADROOM``. ``VMEM_BIT_BUDGET`` (12 MiB) caps the block
below v5e's default 16 MiB scoped VMEM limit (``VMEM_PER_CORE``).

Two matching-bit layouts are supported (see :mod:`repro.core.bitpack`):

* ``packed`` (default) — 32 substreams per int32 word, ``ceil(L/32)``
  words per vertex rounded up to a power of two: 8 bytes per vertex at
  L = 64 (64 vertices per row). Callers see uint8 ``[n, ceil(L/8)]``,
  bit ``j`` of word ``k`` = substream ``8k + j``; the conversion is a
  little-endian split of the 32-bit words at this module's boundary.
* ``unpacked`` — one int32 word (0/1) per substream, ``L`` words per
  vertex rounded up to a power of two (256 bytes per vertex at L = 64).
  Legacy layout, selected with ``SubstreamConfig(mb_layout="unpacked")``
  or ``substream_match(..., packed=False)``; callers see bool ``[n, L]``.

The slot stream (``(u, v, cnt)`` in, ``assigned`` out, int32, double
buffered) lives in SMEM, 1 MiB on v5e; blocks of more than one program
must be a multiple of 1024 slots (SMEM tiles 1-D int32 by 1024).
:func:`vmem_plan` picks ``block_e`` inside both limits.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitpack
from repro.core.types import EdgeStream, MatchingResult, SubstreamConfig
from repro.kernels.substream_match import kernel as _kernel

VMEM_PER_CORE = 16 * 2**20  # v5e default scoped VMEM limit per core
VMEM_BIT_BUDGET = 12 * 2**20  # bytes reserved for the matching-bit block
VMEM_HEADROOM = 2**20  # compiler-internal scratch on top of the bit block
SMEM_PER_CORE = 2**20  # v5e scalar memory per core
#: SMEM bytes per slot of a grid block: (u, v, cnt) in + assigned out,
#: int32, double-buffered by the grid pipeline.
SLOT_SMEM_BYTES = 2 * 4 * (_kernel.SLOT_WORDS + 1)
#: Slots per grid program at most: bounds per-program latency and keeps
#: the slot blocks at a quarter of SMEM.
MAX_BLOCK = 8192
#: SMEM tiles 1-D int32 arrays by 1024 words: a block that is not the
#: whole stream must be a multiple of this many slots.
SMEM_ALIGN = 1024


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _vertex_lanes(L: int, packed: bool) -> int:
    """int32 words per vertex: ``ceil(L/32)`` packed or ``L`` unpacked,
    rounded up to a power of two (so vertices tile a 128-lane row) or,
    past one row, to a multiple of 128."""
    need = -(-max(L, 1) // 32) if packed else max(L, 1)
    if need <= _kernel.LANES:
        return 1 << (need - 1).bit_length()
    return _round_up(need, _kernel.LANES)


@dataclasses.dataclass(frozen=True)
class VmemPlan:
    """Geometry + budget of the VMEM matching-bit block.

    ``n_pad`` is the vertex capacity of the folded block, ``width`` the
    bytes one vertex occupies (``lanes * 4``), ``words`` the caller-format
    row length (``ceil(L/8)`` bytes packed, ``L`` bools unpacked),
    ``nbytes = n_pad * width`` the VMEM the compiler allocates for the
    block (8-sublane, 128-lane tiled), and ``block_e`` the slots per
    grid program (see :func:`vmem_plan` for the selection rule).
    """

    n_pad: int
    width: int
    words: int
    nbytes: int
    block_e: int
    packed: bool

    @property
    def bytes_per_vertex(self) -> int:
        return self.width

    @property
    def lanes(self) -> int:
        """int32 words per vertex."""
        return self.width // 4

    @property
    def bits(self) -> int:
        """Substreams per int32 word: 32 packed, 1 unpacked."""
        return 32 if self.packed else 1

    @property
    def row_width(self) -> int:
        """Lanes per row of the folded block."""
        return max(self.lanes, _kernel.LANES)

    @property
    def rows(self) -> int:
        return self.nbytes // (4 * self.row_width)

    @property
    def vmem_limit(self) -> int:
        """The ``vmem_limit_bytes`` the kernel compiles with."""
        return self.nbytes + VMEM_HEADROOM


def _slot_block(group: int, groups: int) -> int:
    """Tiles of ``group`` slots per grid program: the most that fit
    ``MAX_BLOCK`` slots, rounded to whole ``SMEM_ALIGN`` blocks where
    possible, never more than the ``groups`` the stream has."""
    if group * SLOT_SMEM_BYTES > SMEM_PER_CORE:
        raise ValueError(
            f"a tile of {group} slots needs {group * SLOT_SMEM_BYTES} B of "
            f"slot buffers, more than the {SMEM_PER_CORE} B of SMEM; "
            f"rebuild with a smaller seg_block "
            f"(repro.graph.waves.block_aligned_layout)"
        )
    per = max(1, MAX_BLOCK // group)
    align = SMEM_ALIGN // math.gcd(group, SMEM_ALIGN)
    if per >= align:
        per -= per % align
    return max(1, min(per, max(groups, 1)))


def vmem_plan(
    n: int,
    L: int,
    packed: bool = True,
    block_e: int | None = None,
    m: int | None = None,
) -> VmemPlan:
    """Plan the VMEM bit block for ``n`` vertices and ``L`` substreams.

    The auto ``block_e`` is a power of two, at least 128: ``MAX_BLOCK``,
    or — when the stream length ``m`` is given — the smallest power of
    two covering ``m`` if that is less, so short streams run as one
    program without padding to a huge block. Both choices satisfy the
    SMEM rules (a whole-stream block, or a multiple of ``SMEM_ALIGN``).
    An explicit ``block_e`` under ``SMEM_ALIGN`` on a stream of several
    blocks runs in interpret mode only.
    """
    lanes = _vertex_lanes(L, packed)
    fold = max(_kernel.LANES // lanes, 1)
    rows = _round_up(-(-max(n, 1) // fold), 8)
    words = bitpack.packed_width(max(L, 1)) if packed else max(L, 1)
    if block_e is None:
        block_e = MAX_BLOCK
        if m is not None:
            block_e = min(block_e, 1 << max(m - 1, 1).bit_length())
        block_e = max(128, block_e)
    return VmemPlan(
        n_pad=rows * fold, width=4 * lanes, words=words,
        nbytes=rows * max(lanes, _kernel.LANES) * 4, block_e=block_e,
        packed=packed,
    )


def max_vertices(L: int, packed: bool = True, budget: int = VMEM_BIT_BUDGET) -> int:
    """Largest vertex count whose bit block fits ``budget`` bytes."""
    plan = vmem_plan(1, L, packed=packed)
    row_bytes = 4 * plan.row_width
    return (budget // row_bytes) // 8 * 8 * (plan.n_pad // plan.rows)


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` = auto: compiled on a TPU backend, interpret mode on the
    CPU (the test path). Any other backend cannot run these kernels, so
    auto raises there rather than sliding into the interpreter.

    Explicit True/False always wins (debugging a kernel in interpret
    mode on TPU, or forcing compilation, stays possible). The choice is
    recorded: :func:`substream_match` emits one structured
    ``substream_match.backend`` telemetry event (backend, interpret,
    engine) per call, so bench JSON records which backend actually ran.
    """
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"the Pallas engines run compiled on a TPU or interpreted "
                f"on the CPU; the default backend is {backend!r}"
            )
        return backend != "tpu"
    return bool(interpret)


#: Bytes one slot occupies in the kernel's HBM slot stream: (u, v, cnt)
#: int32 in, assigned int32 out — a single buffer (the double-buffering
#: of ``SLOT_SMEM_BYTES`` is an SMEM *capacity* cost, not extra HBM
#: traffic).
SLOT_STREAM_BYTES = 16


def traffic_bytes(total_slots: int, block_bytes: int, carried: bool = False) -> int:
    """HBM bytes one kernel call moves.

    The slot stream in + assigned out (``SLOT_STREAM_BYTES`` per padded
    slot), and the bit block (``block_bytes``, the plan's ``nbytes``)
    written back once at the end — plus read once at the start when
    carried-in state seeds it (``carried``). The rows a slot touches
    live in VMEM, so they move no HBM bytes. Exact integers from the
    plan accounting, so the ``traffic.hbm_bytes`` counter is
    reproducible bit-exactly.
    """
    return total_slots * SLOT_STREAM_BYTES + block_bytes * (2 if carried else 1)


def plan_counters(plan: VmemPlan) -> dict:
    """The plan-accounting counter set (``plan.*``) for telemetry.

    Bit-exact copies of the :func:`vmem_plan` / :func:`mega_plan`
    fields — tests and the bench gate compare these
    ``==`` against a recomputed plan, so no derived or rounded values.
    """
    out = {
        "plan.n_pad": int(plan.n_pad),
        "plan.width": int(plan.width),
        "plan.words": int(plan.words),
        "plan.bit_block_bytes": int(plan.nbytes),
        "plan.block_e": int(plan.block_e),
        "plan.packed": int(plan.packed),
    }
    if isinstance(plan, WavePlan):
        out.update(
            {
                "plan.seg": int(plan.seg),
                "plan.num_waves": int(plan.num_waves),
                "plan.num_segments": int(plan.num_segments),
                "plan.block_s": int(plan.block_s),
                "plan.gather_bytes": int(plan.gather_bytes),
                "plan.fill": float(plan.fill),
                "plan.seg_block": int(plan.seg_block),
                "plan.num_tiles": int(plan.num_tiles),
                "plan.tiles_per_block": int(plan.tiles_per_block),
                "plan.tile_bytes": int(plan.tile_bytes),
            }
        )
    return out


@dataclasses.dataclass(frozen=True)
class WavePlan(VmemPlan):
    """VmemPlan plus the megakernel's slot-pipeline geometry.

    ``seg`` is the fixed slot count per segment (the schedule's
    fill-packed row width), ``num_waves``/``num_segments`` the layout's
    wave count and block-aligned row count, ``block_s`` how many
    segments one grid program consumes (so ``block_e = block_s * seg``
    slots), and ``fill`` the layout's slot fill (fraction of slots
    holding a real edge). ``seg_block`` segments make one tile (one
    kernel trip), ``num_tiles`` is the layout's tile count,
    ``tiles_per_block`` the tiles per grid program, ``tile_bytes`` one
    buffer of the slot pipeline and ``gather_bytes`` the SMEM the grid
    pipeline allocates for it: ``2 * tile_bytes``, double-buffered so
    the copy of block b+1 overlaps the trips of block b.
    """

    seg: int
    num_waves: int
    num_segments: int
    block_s: int
    gather_bytes: int
    fill: float
    seg_block: int
    num_tiles: int
    tiles_per_block: int
    tile_bytes: int


#: Default segments per megakernel tile: two 8-slot segments make one
#: 16-slot vertex-disjoint tile. Chosen against the interpreter; not yet
#: re-derived on the chip.
MEGA_SEG_BLOCK = 2


def mega_plan(
    n: int,
    L: int,
    layout,
    packed: bool = True,
    tiles_per_block: int | None = None,
) -> WavePlan:
    """Plan the megakernel over ``layout`` (a
    :class:`repro.graph.waves.BlockAlignedLayout`).

    The bit block is :func:`vmem_plan`'s. One trip handles one tile
    (``seg_block * seg`` vertex-disjoint slots), so nothing per trip is
    allocated: ``tile_bytes`` is one buffer of the slot pipeline's SMEM
    blocks and ``gather_bytes`` the double-buffered pair. The auto
    ``tiles_per_block`` fills ``MAX_BLOCK`` slots, clamped to the
    layout's tile count and ``SMEM_ALIGN``-aligned where the stream
    spans several programs; the fallback ladder passes 1 for its
    smallest slot working set. Errors name the knob that must change.
    """
    seg = int(layout.width)
    seg_block = int(layout.seg_block)
    bslots = seg_block * seg
    num_tiles = int(layout.num_tiles)
    base = vmem_plan(n, L, packed=packed, block_e=1)
    if tiles_per_block is None:
        tiles_per_block = _slot_block(bslots, num_tiles)
    tile_bytes = tiles_per_block * bslots * SLOT_SMEM_BYTES // 2
    if 2 * tile_bytes > SMEM_PER_CORE:
        raise ValueError(
            f"slot-stream blocks ({2 * tile_bytes} B at "
            f"tiles_per_block={tiles_per_block}, seg_block={seg_block}, "
            f"seg={seg}) exceed the {SMEM_PER_CORE} B of SMEM; lower "
            f"tiles_per_block (ops.mega_plan) or seg_block"
        )
    return WavePlan(
        n_pad=base.n_pad,
        width=base.width,
        words=base.words,
        nbytes=base.nbytes,
        block_e=tiles_per_block * bslots,
        packed=packed,
        seg=seg,
        num_waves=int(layout.seg_offsets.shape[0] - 1),
        num_segments=int(layout.num_segments),
        block_s=tiles_per_block * seg_block,
        gather_bytes=2 * tile_bytes,
        fill=float(layout.fill),
        seg_block=seg_block,
        num_tiles=num_tiles,
        tiles_per_block=tiles_per_block,
        tile_bytes=tile_bytes,
    )


def _resolve_packed(cfg: SubstreamConfig, packed: bool | None) -> bool:
    if packed is None:
        if cfg.mb_layout not in ("packed", "unpacked"):
            raise ValueError(f"unknown mb_layout {cfg.mb_layout!r}")
        packed = cfg.mb_layout != "unpacked"
    return packed


class EngineFallbackWarning(RuntimeWarning):
    """An engine of the ``on_plan_failure="fallback"`` cascade failed and
    the next one was tried."""


class FallbackExhaustedError(RuntimeError):
    """Every engine in the fallback cascade failed.

    ``attempts`` is the ordered ``(engine_label, exception)`` list, so a
    service log shows the whole degradation path in one line.
    """

    def __init__(self, attempts):
        self.attempts = tuple(attempts)
        lines = "; ".join(
            f"{label}: {type(err).__name__}: {err}" for label, err in self.attempts
        )
        super().__init__(f"all fallback engines failed ({lines})")


def _empty_result(stream: EdgeStream, cfg: SubstreamConfig, packed: bool):
    """Well-formed nothing-matched result (n == 0 vertex spaces)."""
    assigned = jnp.full((stream.num_edges,), -1, jnp.int32)
    if packed:
        words = bitpack.packed_width(max(cfg.L, 1))
        return MatchingResult(
            assigned=assigned,
            mb_packed=jnp.zeros((0, words), jnp.uint8),
            L=cfg.L,
        )
    return MatchingResult(assigned=assigned, mb=jnp.zeros((0, cfg.L), bool))


def _mb0_dense(mb0, cfg: SubstreamConfig, packed: bool):
    """Caller-format initial bits as the dense bool [n, L] the XLA
    engines consume."""
    if mb0 is None:
        return None
    if packed:
        return bitpack.unpack_bits(jnp.asarray(mb0), cfg.L)
    return jnp.asarray(mb0).astype(bool)


def _repack(result: MatchingResult, packed: bool) -> MatchingResult:
    """Convert a dense XLA-fallback result to the storage the caller asked
    for, so cascade consumers see the same ``is_packed`` contract as the
    Pallas engines (`mb`/`assigned` are bit-identical either way)."""
    if packed and not result.is_packed:
        return MatchingResult(
            assigned=result.assigned,
            mb_packed=bitpack.pack_bits(result.mb),
            L=result.L,
        )
    return result


def _run_engine(
    engine: str,
    stream: EdgeStream,
    cfg: SubstreamConfig,
    *,
    block_e,
    interpret,
    packed,
    waves,
    seg_block,
    telemetry,
    mb0=None,
    tiles_per_block=None,
) -> MatchingResult:
    """Dispatch one concrete engine of the cascade. The XLA fallbacks are
    looked up through the module at call time (not from-imported), so the
    fault injector can force them to fail too. ``mb0`` (caller storage:
    uint8 [n, words] packed / bool [n, L] dense) seeds the matching bits;
    the XLA rungs take the dense view. ``tiles_per_block`` is the
    ladder's own megakernel knob (``None``: the plan's auto pick)."""
    if engine == "mega":
        return _substream_match_mega(
            stream, cfg, interpret=interpret, packed=packed, waves=waves,
            seg_block=seg_block, telemetry=telemetry, mb0=mb0,
            tiles_per_block=tiles_per_block,
        )
    if engine == "edges":
        return _edges_entry(
            stream, cfg, block_e=block_e, interpret=interpret, packed=packed,
            telemetry=telemetry, mb0=mb0,
        )
    from repro.core import matching as _matching

    if engine == "waves_xla":
        return _repack(
            _matching.mwm_waves(
                stream, cfg, schedule=waves, telemetry=telemetry,
                mb0=_mb0_dense(mb0, cfg, packed),
            ),
            packed,
        )
    if engine == "scan":
        return _repack(
            _matching.mwm_scan(stream, cfg, mb0=_mb0_dense(mb0, cfg, packed)),
            packed,
        )
    if engine == "ref":
        from repro.kernels.substream_match import ref as _ref

        w = jnp.where(stream.valid, stream.weight.astype(jnp.float32), 0.0)
        thr = cfg.thresholds()
        init = None if mb0 is None else jnp.asarray(mb0)
        if packed:
            assigned, mb = _ref.substream_match_ref_packed(
                stream.src, stream.dst, w, thr, cfg.n, mb0=init
            )
            return MatchingResult(assigned=assigned, mb_packed=mb, L=cfg.L)
        assigned, mb = _ref.substream_match_ref(
            stream.src, stream.dst, w, thr, cfg.n, mb0=init
        )
        return MatchingResult(assigned=assigned, mb=mb.astype(bool))
    raise ValueError(f"unknown engine {engine!r}")


def _fallback_attempts(schedule: str, seg_block):
    """The ordered degradation ladder for ``on_plan_failure="fallback"``:
    shrink the megakernel's slot working set first — one segment per
    tile, then one tile per grid program — then step down to waves_xla
    and scan. No rung repeats the program of an earlier one. Each entry
    is ``(engine, {knob overrides}, label)``."""
    xla = [("waves_xla", {}, "waves_xla"), ("scan", {}, "scan")]
    if schedule == "edges":
        return [("edges", {}, "edges")] + xla
    attempts = [("mega", {}, "mega")]
    if (MEGA_SEG_BLOCK if seg_block is None else seg_block) != 1:
        attempts.append(("mega", {"seg_block": 1}, "mega[seg_block=1]"))
    attempts.append((
        "mega", {"seg_block": 1, "tiles_per_block": 1},
        "mega[seg_block=1,tiles_per_block=1]",
    ))
    return attempts + xla


def _substream_match_fallback(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    *,
    block_e,
    interpret,
    packed,
    schedule,
    waves,
    seg_block,
    telemetry,
    mb0=None,
) -> MatchingResult:
    """The fallback cascade resolver (``on_plan_failure="fallback"``).

    Runs the :func:`_fallback_attempts` ladder until an engine returns a
    result. Every failure is observable: an :class:`EngineFallbackWarning`
    (always, telemetry on or off), a ``fallback`` instant event
    (from_engine, to_engine, reason) plus the ``fallback.count`` session
    counter, and each degraded attempt runs inside a ``fallback`` span.
    The per-call :class:`repro.obs.MatchTelemetry` record of the engine
    that finally succeeded carries ``fallback.count`` (0 on the clean
    path — the bench gate pins that). Validation and invariant errors
    are *not* absorbed: a bad stream fails every engine identically, so
    retrying would only mask the caller's bug.
    """
    from repro.core import guard as _guard

    attempts = _fallback_attempts(schedule, seg_block)
    failures = []
    for idx, (engine, overrides, label) in enumerate(attempts):
        kw = {"seg_block": seg_block, **overrides}
        ncalls = len(telemetry.match_calls)
        span = (
            telemetry.span("fallback", engine=label, attempt=idx)
            if failures
            else obs.NULL_SPAN
        )
        try:
            with span:
                out = _run_engine(
                    engine, stream, cfg, block_e=block_e, interpret=interpret,
                    packed=packed, waves=waves, telemetry=telemetry, mb0=mb0,
                    **kw,
                )
        except (_guard.StreamValidationError, _guard.MatchingInvariantError):
            raise
        except Exception as err:  # noqa: BLE001 — availability cascade
            failures.append((label, err))
            nxt = attempts[idx + 1][2] if idx + 1 < len(attempts) else None
            # never silent: the warning fires with telemetry off too, so a
            # refused kernel cannot pass for the engine that replaced it
            warnings.warn(
                f"engine {label} failed ({type(err).__name__}: {err}); "
                f"falling back to {nxt}",
                EngineFallbackWarning,
                stacklevel=3,
            )
            if telemetry.enabled:
                telemetry.event(
                    "fallback",
                    from_engine=label,
                    to_engine=nxt,
                    reason=f"{type(err).__name__}: {err}"[:500],
                )
                telemetry.counters.add("fallback.count")
            if idx + 1 == len(attempts):
                raise FallbackExhaustedError(failures) from err
            continue
        if telemetry.enabled and len(telemetry.match_calls) > ncalls:
            # stamp the degradation depth onto the per-call record of the
            # engine that actually produced the result (0 = clean path)
            telemetry.match_calls[-1].counters["fallback.count"] = len(failures)
        return out
    raise FallbackExhaustedError(failures)


def substream_match(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    block_e: int | None = None,
    interpret: bool | None = None,
    packed: bool | None = None,
    schedule: str = "edges",
    waves=None,
    seg_block: int | None = None,
    telemetry=obs.DISABLED,
    on_plan_failure: str = "raise",
    validate: str = "off",
    mb0: jax.Array | None = None,
) -> MatchingResult:
    """Run Part 1 on the given stream order via the Pallas kernel.

    ``mb0`` seeds the matching bits with carried-in state (the epoch
    executor's resume path; see :func:`match_epochs`) — uint8
    ``[n, ceil(L/8)]`` when ``packed``, bool ``[n, L]`` otherwise.
    ``None`` (the default) is the plain zero-state run and leaves every
    jit cache key and kernel call graph byte-identical to before.

    ``schedule`` picks the pipeline:

    * ``"edges"`` — the paper-faithful 1-edge-per-iteration processor;
    * ``"mega"`` — the megakernel: the stream is first decomposed into
      vertex-disjoint waves (``repro.graph.waves``), packed into
      ``seg``-slot segments and re-padded block-aligned (every tile of
      ``seg_block`` segments is a subset of one wave, hence
      vertex-disjoint); each kernel trip loads the rows of one whole
      tile before it stores any. Bit-identical to ``"edges"`` (greedy
      matching is confluent over vertex-disjoint edges), with
      ~``seg_block * seg``x fewer sequential trips; ``seg_block=None``
      takes :data:`MEGA_SEG_BLOCK`. Pass a precomputed ``waves``
      schedule to amortize the decomposition across runs.

    ``packed=None`` follows ``cfg.mb_layout``; ``block_e=None`` takes the
    auto-picked value from :func:`vmem_plan` (edges schedule only).
    ``interpret=None`` = auto: compiled on a TPU backend, interpreted on
    the CPU (:func:`resolve_interpret`). The packed result carries
    ``mb_packed`` (uint8 bit planes) and unpacks to the bool ``mb`` view
    lazily; both layouts are bit-identical in ``assigned`` and ``mb``.

    ``telemetry`` (a :class:`repro.obs.Telemetry`; default: the no-op
    :data:`repro.obs.DISABLED`) records one ``substream_match.backend``
    event naming the backend that actually ran, stage spans
    (schedule/pack/layout/compile/execute), the plan/schedule counters,
    and a per-call :class:`repro.obs.MatchTelemetry` appended to
    ``telemetry.match_calls``.

    ``validate`` is the input-guard policy (``"off"`` default — zero
    overhead for trusted paths; ``"strict"`` raises on malformed
    streams, ``"sanitize"`` drops bad edges and reports via counters —
    see :func:`repro.core.guard.validate_stream`).

    ``on_plan_failure`` picks what happens when a plan exceeds VMEM or
    the Pallas path fails: ``"raise"`` (default, today's behavior)
    propagates; ``"fallback"`` degrades through the cascade — the
    megakernel at one segment per tile, then at one tile per grid
    program, then ``waves_xla`` -> the scan oracle — emitting an
    :class:`EngineFallbackWarning` and ``fallback`` spans/events/counters
    so the degradation is observable, never silent.

    With ``on_plan_failure="raise"``, raises if the bit block exceeds
    the VMEM budget — at that size the caller must vertex-partition
    (core.rounds) instead.
    """
    if validate != "off":
        from repro.core import guard as _guard

        stream, _ = _guard.validate_stream(
            stream, cfg.n, policy=validate, telemetry=telemetry
        )
    interpret = resolve_interpret(interpret)
    packed = _resolve_packed(cfg, packed)
    if schedule not in ("edges", "mega"):
        raise ValueError(f"unknown schedule {schedule!r}; use 'edges' or 'mega'")
    if on_plan_failure not in ("raise", "fallback"):
        raise ValueError(
            f"unknown on_plan_failure {on_plan_failure!r}; "
            f"use 'raise' or 'fallback'"
        )
    if telemetry.enabled:
        telemetry.event(
            "substream_match.backend",
            engine=schedule,
            backend=jax.default_backend(),
            interpret=bool(interpret),
        )
    if cfg.n == 0:
        return _empty_result(stream, cfg, packed)
    if on_plan_failure == "fallback":
        return _substream_match_fallback(
            stream, cfg, block_e=block_e, interpret=interpret, packed=packed,
            schedule=schedule, waves=waves, seg_block=seg_block,
            telemetry=telemetry, mb0=mb0,
        )
    if schedule == "edges":
        return _edges_entry(
            stream, cfg, block_e=block_e, interpret=interpret, packed=packed,
            telemetry=telemetry, mb0=mb0,
        )
    return _substream_match_mega(
        stream, cfg, interpret=interpret, packed=packed,
        waves=waves, seg_block=seg_block, telemetry=telemetry, mb0=mb0,
    )


def _to_block(mb0, plan: VmemPlan, cfg: SubstreamConfig) -> jax.Array:
    """Caller-format initial bits (uint8 [n, ceil(L/8)] packed / bool
    [n, L] dense) -> the kernel's folded int32 [rows, row_width] block.
    Packed bytes combine little-endian into 32-bit words (byte k of a
    vertex -> bits 8*(k%4).. of word k//4), so substream 8k+j stays bit
    j of byte k. Padding vertices and lanes are zero."""
    n = cfg.n
    if plan.packed:
        b = jnp.zeros((n, 4 * plan.lanes), jnp.uint32)
        b = b.at[:, : plan.words].set(jnp.asarray(mb0).astype(jnp.uint32))
        b = b.reshape(n, plan.lanes, 4) << jnp.arange(0, 32, 8, dtype=jnp.uint32)
        words = jax.lax.bitcast_convert_type(b.sum(axis=2, dtype=jnp.uint32), jnp.int32)
    else:
        words = jnp.zeros((n, plan.lanes), jnp.int32)
        words = words.at[:, : cfg.L].set(jnp.asarray(mb0).astype(jnp.int32))
    block = jnp.zeros((plan.n_pad, plan.lanes), jnp.int32).at[:n].set(words)
    return block.reshape(plan.rows, plan.row_width)


def _from_block(mb: jax.Array, plan: VmemPlan, cfg: SubstreamConfig):
    """The kernel's folded block -> caller format: uint8 [n, ceil(L/8)]
    (little-endian bytes of the 32-bit words) packed, bool [n, L]
    unpacked. Inverse of :func:`_to_block` on the first L bits."""
    words = mb.reshape(plan.n_pad, plan.lanes)[: cfg.n]
    if not plan.packed:
        return words[:, : cfg.L] != 0
    u = jax.lax.bitcast_convert_type(words, jnp.uint32)
    b = (u[:, :, None] >> jnp.arange(0, 32, 8, dtype=jnp.uint32)) & 0xFF
    return b.reshape(cfg.n, 4 * plan.lanes)[:, : plan.words].astype(jnp.uint8)


def _match_slots(u, v, w, num_groups, cfg, plan, group, groups_per_block,
                 interpret, mb0):
    """Traced core shared by the two engines: Stage 4's threshold
    count per slot (0 for self-loops and w <= 0), the kernel over the
    three (u, v, cnt) slot streams, and the caller-format bits."""
    thr = cfg.thresholds()
    cnt = jnp.sum(w[:, None] >= thr[None, :], axis=1, dtype=jnp.int32)
    cnt = jnp.where(u != v, cnt, 0)
    assigned, mb = _kernel.substream_match_pallas(
        u,
        v,
        cnt,
        jnp.full((1,), num_groups, jnp.int32),
        rows=plan.rows,
        width=plan.row_width,
        lanes=plan.lanes,
        bits=plan.bits,
        group=group,
        groups_per_block=groups_per_block,
        vmem_limit=plan.vmem_limit,
        interpret=interpret,
        mb_init=None if mb0 is None else _to_block(mb0, plan, cfg),
    )
    return assigned, _from_block(mb, plan, cfg)


def _result(assigned, mb, cfg: SubstreamConfig, packed: bool) -> MatchingResult:
    if packed:
        return MatchingResult(assigned=assigned, mb_packed=mb, L=cfg.L)
    return MatchingResult(assigned=assigned, mb=mb)


def _check_budget(plan: VmemPlan) -> None:
    if plan.nbytes > VMEM_BIT_BUDGET:
        raise ValueError(
            f"matching-bit block {plan.nbytes/2**20:.1f} MiB > VMEM budget; "
            f"use repro.core.rounds with vertex partitioning"
        )


def _edges_entry(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    block_e: int | None,
    interpret: bool,
    packed: bool,
    telemetry,
    mb0=None,
) -> MatchingResult:
    """Telemetry shell of the per-edge engine (the jitted body is
    :func:`_substream_match_edges`). The edges path has no host
    scheduling, so schedule/pack/layout stages stay 0; its kernel makes
    one trip per stream position (``kernel.trips`` = m)."""
    m = stream.num_edges
    rec = obs.recorder(
        telemetry, "pallas_edges", m, jax.default_backend(), interpret
    )
    if telemetry.enabled:
        plan = vmem_plan(cfg.n, cfg.L, packed=packed, block_e=block_e, m=m)
        m_pad = _round_up(max(m, 1), plan.block_e)
        rec.put_many(plan_counters(plan))
        rec.put("stream.num_edges", m)
        rec.put("kernel.trips", m)
        rec.put(
            "traffic.hbm_bytes", traffic_bytes(m_pad, plan.nbytes, mb0 is not None)
        )
    key = (
        "edges", cfg.n, cfg.L, cfg.eps, packed, interpret, block_e, m,
        mb0 is not None,
    )
    with rec.device_stage(key):
        if mb0 is not None:
            with rec.span("copy.h2d", what="mb0"):
                mb0 = rec.block(jnp.asarray(mb0))
        out = _substream_match_edges(
            stream, cfg, block_e=block_e, interpret=interpret, packed=packed,
            mb0=mb0,
        )
        rec.block(out)
    rec.finish()
    return out


@partial(jax.jit, static_argnames=("cfg", "block_e", "interpret", "packed"))
def _substream_match_edges(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    block_e: int | None,
    interpret: bool,
    packed: bool,
    mb0: jax.Array | None = None,
) -> MatchingResult:
    """The per-edge engine: every stream position is a group of one slot,
    in stream order."""
    m = stream.num_edges
    plan = vmem_plan(cfg.n, cfg.L, packed=packed, block_e=block_e, m=m)
    _check_budget(plan)
    # empty streams still run one block, so the kernel's init executes
    # and mb comes back zeroed; the trip bound (m) skips the padding
    pad = _round_up(max(m, 1), plan.block_e) - m
    u = jnp.pad(stream.src.astype(jnp.int32), (0, pad))
    v = jnp.pad(stream.dst.astype(jnp.int32), (0, pad))
    # invalid edges -> weight 0 (< every threshold, since thresholds >= 1)
    w = jnp.where(stream.valid, stream.weight.astype(jnp.float32), 0.0)
    w = jnp.pad(w, (0, pad))
    assigned, mb = _match_slots(
        u, v, w, m, cfg, plan, 1, plan.block_e, interpret, mb0
    )
    return _result(assigned[:m], mb, cfg, packed)


@partial(
    jax.jit,
    static_argnames=("cfg", "plan", "group", "interpret"),
)
def _slots_device(u, v, w, num_groups, cfg, plan, group, interpret, mb0=None):
    """Jitted device half of the mega path: run the kernel over
    the host-prepped slot stream (grid-padded, groups vertex-disjoint;
    padding slots are ``u = v = 0, w = 0``). ``mb0`` (caller storage)
    seeds the bit block."""
    return _match_slots(
        u, v, w, num_groups, cfg, plan, group, plan.block_e // group,
        interpret, mb0,
    )


# A module attribute of its own, so a fault injector can fail the mega
# path's device half.
_mega_device = _slots_device


def _kernel_plan(plan: VmemPlan) -> VmemPlan:
    """The shape-determining part of a plan (a static jit argument that
    does not change with the schedule's counters)."""
    return VmemPlan(
        n_pad=plan.n_pad, width=plan.width, words=plan.words,
        nbytes=plan.nbytes, block_e=plan.block_e, packed=plan.packed,
    )


def _schedule_for(stream, waves, telemetry, rec):
    """Resolve the wave schedule, recording its stage times."""
    from repro.graph import waves as _waves

    if waves is None:
        # built in-call, its links on the device that holds the stream:
        # the schedule's own stopwatch measurements are the stage split
        # (assign -> "schedule", layout -> "pack")
        sch = _waves.wave_schedule(
            stream.src, stream.dst, valid=stream.valid, telemetry=telemetry
        )
        rec.add_stage("schedule", sch.schedule_seconds)
        rec.add_stage("pack", sch.pack_seconds)
        return sch
    with rec.span("copy.d2h", what="stream"):
        src = np.asarray(stream.src)
        dst = np.asarray(stream.dst)
        valid = np.asarray(stream.valid)
    with rec.stage("schedule"):  # precomputed: validation cost only
        return _waves.resolve_schedule(
            src, dst, valid, schedule=waves, telemetry=telemetry
        )


def _run_slot_layout(
    stream, cfg, plan, slots, num_groups, group, device, engine, rec,
    interpret, packed, mb0,
):
    """Host half of the mega path. ``slots`` is the
    [rows, seg] slot -> stream-position map (-1 = padding), already
    grouped so each run of ``group`` slots is vertex-disjoint: gather
    the slot stream (padding -> ``u = v = 0, w = 0``), pad it to whole
    grid programs, run ``device``, and scatter the per-slot assignments
    back to stream positions. The host<->device copies are ``copy.*``
    spans beside the ``layout.gather`` / ``layout.scatter`` work."""
    with rec.stage("layout"):
        with rec.span("copy.d2h", what="stream"):
            src = np.asarray(stream.src)
            dst = np.asarray(stream.dst)
            valid = np.asarray(stream.valid)
            weight = np.asarray(stream.weight)
        with rec.span("layout.gather"):
            flat = slots.reshape(-1)
            live = flat >= 0
            pos = flat[live]
            total = _round_up(max(flat.size, 1), plan.block_e)
            u = np.zeros(total, np.int32)
            v = np.zeros(total, np.int32)
            w = np.zeros(total, np.float32)
            lv = np.zeros(total, bool)
            lv[: flat.size] = live
            u[lv] = src[pos]
            v[lv] = dst[pos]
            w[lv] = np.where(valid[pos], weight[pos].astype(np.float32), 0.0)
    key = (
        engine, group, _kernel_plan(plan), interpret, total, cfg,
        mb0 is not None,
    )
    with rec.device_stage(key):
        with rec.span("copy.h2d", what="slots"):
            u, v, w = rec.block((jnp.asarray(u), jnp.asarray(v), jnp.asarray(w)))
            if mb0 is not None:
                mb0 = rec.block(jnp.asarray(mb0))
        assigned_slots, mb = device(
            u, v, w, num_groups, cfg, _kernel_plan(plan), group, interpret,
            mb0=mb0,
        )
        rec.block((assigned_slots, mb))
    with rec.stage("layout"):
        with rec.span("copy.d2h", what="assigned_slots"):
            assigned_slots = np.asarray(assigned_slots)
        with rec.span("layout.scatter"):
            # slot -> stream-position scatter on the host: each stream
            # position occupies exactly one slot, so a plain indexed store
            assigned = np.full(stream.num_edges, -1, np.int32)
            assigned[pos] = assigned_slots[: flat.size][live]
    with rec.span("copy.h2d", what="assigned"):
        assigned = rec.block(jnp.asarray(assigned))
    return total, _result(assigned, mb, cfg, packed)


def _substream_match_mega(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    interpret: bool,
    packed: bool,
    waves=None,
    seg_block: int | None = None,
    telemetry=obs.DISABLED,
    mb0=None,
    tiles_per_block: int | None = None,
) -> MatchingResult:
    from repro.graph import waves as _waves

    if seg_block is None:
        seg_block = MEGA_SEG_BLOCK
    rec = obs.recorder(
        telemetry, "pallas_mega", stream.num_edges,
        jax.default_backend(), interpret,
    )
    sch = _schedule_for(stream, waves, telemetry, rec)
    with rec.stage("layout"), rec.span("layout.block_align"):
        layout = _waves.block_aligned_layout(sch, seg_block)
    plan = mega_plan(
        cfg.n, cfg.L, layout, packed=packed, tiles_per_block=tiles_per_block
    )
    _check_budget(plan)
    total, out = _run_slot_layout(
        stream, cfg, plan, layout.slots, layout.num_tiles,
        seg_block * plan.seg, _mega_device, "mega", rec, interpret, packed,
        mb0,
    )
    if telemetry.enabled:
        rec.put_many(_waves.schedule_counters(sch))
        rec.put_many(_waves.layout_counters(layout, sch))
        rec.put_many(plan_counters(plan))
        rec.put("stream.num_edges", stream.num_edges)
        rec.put("kernel.trips", plan.num_tiles)
        rec.put(
            "traffic.hbm_bytes", traffic_bytes(total, plan.nbytes, mb0 is not None)
        )
    rec.finish()
    return out


# --------------------------------------------------------------------------
# Resumable chunked execution.

#: Engines :func:`match_epochs` can drive. The Pallas schedules go
#: through :func:`substream_match`'s machinery; ``scan`` / ``waves_xla``
#: are the XLA engines and ``ref`` the pure-jnp oracle — all accept the
#: carried ``mb0`` state, so every engine is epoch-chunkable.
EPOCH_ENGINES = ("edges", "mega", "scan", "waves_xla", "ref")


def epoch_bounds(num_edges: int, epochs: int) -> list[int]:
    """Stream positions of the epoch barriers: ``epochs + 1`` monotone
    bounds with near-equal slices (``round(i * m / E)``). Fixed by
    ``(m, E)`` alone, so a resumed run recomputes identical barriers —
    snapshots taken by the crashed run land exactly on them."""
    if epochs < 1:
        raise ValueError(f"epochs must be >= 1, got {epochs}")
    return [round(i * num_edges / epochs) for i in range(epochs + 1)]


def match_epochs(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    *,
    epochs: int = 1,
    engine: str = "mega",
    state=None,
    snapshots=None,
    guard=None,
    packed: bool | None = None,
    interpret: bool | None = None,
    telemetry=obs.DISABLED,
    validate: str = "off",
    on_plan_failure: str = "raise",
    block_e: int | None = None,
    seg_block: int | None = None,
    epoch_hook=None,
) -> MatchingResult:
    """Run Part 1 chunked into ``epochs`` resumable epochs.

    The stream is split at :func:`epoch_bounds`; each epoch runs
    ``engine`` (one of :data:`EPOCH_ENGINES`) on its slice with the
    carried matching bits as ``mb0`` and folds the result into a
    :class:`repro.core.state.MatchState`. Epoch boundaries are barriers,
    so wave scheduling only sees within-epoch conflict chains, and the
    result is **bit-identical to the one-shot run** for every engine:
    greedy matching is confluent in the carried bits, and the recorded
    ``assigned`` slices concatenate (see ``docs/paper_map.md``).

    Resumability:

    * ``snapshots`` (a :class:`repro.checkpoint.snapshots
      .SnapshotManager`) commits the state after every epoch and, when
      ``state`` is not given, resumes from the latest committed
      snapshot — validating its fingerprint against *this* (stream,
      cfg, storage) and replaying only the remaining suffix;
    * ``state`` injects carried state directly (serving-style warm
      resumes); its fingerprint is validated the same way;
    * ``guard`` (a :class:`repro.core.executor.ExecutionGuard`) wraps
      each epoch's device work: per-epoch deadline, bounded retries
      with exponential backoff on transient faults, straggler EWMA.
      Permanent faults are the fallback cascade's job — pass
      ``on_plan_failure="fallback"`` to degrade engines inside the
      epoch instead of failing it.

    ``epoch_hook(epoch_index, state)`` fires after each epoch's
    snapshot commit — the crash-injection seam for the recovery tests
    (faultline's ``kill_at_epoch``). Telemetry: one ``epoch.index``
    event per executed epoch plus the ``epoch.count`` counter;
    ``epochs=1`` with no snapshots/guard is exactly a one-shot call.
    """
    if engine not in EPOCH_ENGINES:
        raise ValueError(f"unknown engine {engine!r}; use {EPOCH_ENGINES}")
    if on_plan_failure not in ("raise", "fallback"):
        raise ValueError(
            f"unknown on_plan_failure {on_plan_failure!r}; "
            f"use 'raise' or 'fallback'"
        )
    if validate != "off":
        from repro.core import guard as _guard

        stream, _ = _guard.validate_stream(
            stream, cfg.n, policy=validate, telemetry=telemetry
        )
    interpret = resolve_interpret(interpret)
    packed = _resolve_packed(cfg, packed)
    if cfg.n == 0:
        return _empty_result(stream, cfg, packed)
    from repro.core.state import MatchState

    with telemetry.span("state.initial"):
        template = MatchState.initial(stream, cfg, packed)
    if state is None and snapshots is not None:
        state = snapshots.latest(template)
    if state is None:
        state = template
    elif state.fingerprint != template.fingerprint:
        from repro.checkpoint.snapshots import SnapshotMismatchError

        raise SnapshotMismatchError(
            f"carried state fingerprints {state.fingerprint!r}, run "
            f"fingerprints {template.fingerprint!r} — different stream, "
            f"config, or storage layout"
        )
    m = stream.num_edges
    bounds = epoch_bounds(m, epochs)
    fallback = on_plan_failure == "fallback" and engine in ("edges", "mega")
    for k in range(epochs):
        a, b = max(bounds[k], state.pos), bounds[k + 1]
        if b <= state.pos:
            continue  # already durable in the carried state
        sub = EdgeStream(
            src=stream.src[a:b],
            dst=stream.dst[a:b],
            weight=stream.weight[a:b],
            valid=stream.valid[a:b],
        )
        telemetry.event(
            "epoch.index", epoch=k, start=a, end=b, engine=engine,
        )
        telemetry.count("epoch.count")
        mb0 = state.mb0

        def run_one(sub=sub, mb0=mb0):
            if fallback:
                return _substream_match_fallback(
                    sub, cfg, block_e=block_e, interpret=interpret,
                    packed=packed, schedule=engine, waves=None,
                    seg_block=seg_block, telemetry=telemetry, mb0=mb0,
                )
            return _run_engine(
                engine, sub, cfg, block_e=block_e, interpret=interpret,
                packed=packed, waves=None, seg_block=seg_block,
                telemetry=telemetry, mb0=mb0,
            )

        out = guard.run(run_one, label=f"epoch[{k}]") if guard else run_one()
        with telemetry.span("epoch.fold"):
            state = state.advance(out, b)
        if snapshots is not None:
            snapshots.save(state)
        if epoch_hook is not None:
            epoch_hook(k, state)
    if snapshots is not None:
        snapshots.wait()
    return state.result()

"""Per-call telemetry records for the matching engines.

A :class:`MatchTelemetry` is the aggregate of ONE ``substream_match``
(or XLA-engine) call: which engine/backend actually ran, the host
stage split, the counter snapshot, and the derived rate. The stages:

``schedule``
    Host wave-schedule assignment (conflict-depth / earliest-fit), or —
    when a precomputed schedule was passed in — its validation cost.
``pack``
    Host fill-packed slot layout of a schedule built in-call (0.0 when
    the schedule was precomputed).
``layout``
    Host per-call stream prep: block-aligned re-padding (mega), slot
    array gather, grid padding, and the slot→stream scatter-back.
``compile``
    Wall time of the device call when its jit variant — keyed by
    ``(engine, seg, width, L, shapes, ...)`` — was dispatched for the
    first time in this process. Dominated by tracing + XLA compilation
    but *includes the first execution* (JAX offers no portable split of
    the two inside one dispatch); steady-state calls report 0 here.
``execute``
    Wall time (``block_until_ready``) of the device call when the
    variant was already compiled; 0 on the compile call.

Stage seconds are disjoint wall-clock intervals of the same call, so
``sum(stage_seconds.values()) <= wall_seconds`` always — checked by
:func:`consistency_problems`, which the bench gate reuses.

Engines build records through :func:`recorder`; its disabled twin
(:data:`NULL_RECORDER`) makes every instrumentation site a no-op when
telemetry is off.
"""
from __future__ import annotations

import dataclasses
import time

from repro.obs.counters import variant_seen
from repro.obs.trace import NULL_SPAN, Span

#: The canonical stage keys, in pipeline order. Every MatchTelemetry
#: (and every bench ``stage_seconds`` row) carries exactly these.
STAGES = ("schedule", "pack", "layout", "compile", "execute")

#: Counter names every wave/mega engine record must carry (the plan
#: accounting the bench gate cross-checks bit-exactly).
PLAN_COUNTERS = ("plan.gather_bytes", "plan.bit_block_bytes")


@dataclasses.dataclass(frozen=True)
class MatchTelemetry:
    """Aggregated telemetry of one matching-engine call."""

    engine: str
    backend: str
    interpret: bool
    num_edges: int
    wall_seconds: float
    stage_seconds: dict
    counters: dict

    @property
    def edges_per_sec(self) -> float:
        """Full-call rate (host + device) — the number the bench reports."""
        return self.num_edges / self.wall_seconds if self.wall_seconds > 0 else 0.0

    @property
    def device_seconds(self) -> float:
        return self.stage_seconds.get("compile", 0.0) + self.stage_seconds.get(
            "execute", 0.0
        )

    def asdict(self) -> dict:
        """JSON-ready dict (stages in canonical order, sorted counters)."""
        return {
            "engine": self.engine,
            "backend": self.backend,
            "interpret": self.interpret,
            "num_edges": self.num_edges,
            "wall_seconds": self.wall_seconds,
            "edges_per_sec": self.edges_per_sec,
            "stage_seconds": {s: self.stage_seconds.get(s, 0.0) for s in STAGES},
            "counters": {k: self.counters[k] for k in sorted(self.counters)},
        }


def consistency_problems(
    stage_seconds: dict, wall_seconds: float, rel_slack: float = 0.02,
    abs_slack: float = 1e-4,
) -> list[str]:
    """Internal-consistency check shared by tests and the bench gate.

    Returns human-readable problem strings (empty = consistent):
    missing stage keys, negative stages, or stage sums exceeding the
    call's wall time beyond slack (stages are disjoint sub-intervals of
    the wall interval, so their sum can never legitimately exceed it).
    """
    problems = []
    missing = [s for s in STAGES if s not in stage_seconds]
    if missing:
        problems.append(f"missing stage keys {missing}")
    negative = {s: v for s, v in stage_seconds.items() if v < 0}
    if negative:
        problems.append(f"negative stage seconds {negative}")
    total = sum(v for v in stage_seconds.values() if v > 0)
    if total > wall_seconds * (1 + rel_slack) + abs_slack:
        problems.append(
            f"stage sum {total:.6f}s exceeds wall {wall_seconds:.6f}s"
        )
    return problems


class MatchRecorder:
    """Accumulates one engine call's stages/counters into a record.

    Created via :func:`recorder` at engine entry; ``finish()`` seals
    the record, appends it to ``telemetry.match_calls``, and folds the
    session-level aggregates (call counts, jit hit/miss totals) into
    the telemetry counter registry.
    """

    __slots__ = (
        "_telemetry", "engine", "backend", "interpret", "num_edges",
        "stage_seconds", "counters", "_t0",
    )

    def __init__(self, telemetry, engine, num_edges, backend, interpret):
        self._telemetry = telemetry
        self.engine = engine
        self.backend = backend
        self.interpret = interpret
        self.num_edges = num_edges
        self.stage_seconds = dict.fromkeys(STAGES, 0.0)
        self.counters: dict = {}
        # every span from here to the next engine call carries this index
        telemetry.tracer.call = len(telemetry.match_calls)
        self._t0 = time.perf_counter()

    def stage(self, name: str) -> Span:
        """``with rec.stage("layout"): ...`` — credit the block to a stage
        (recorded as the span ``{engine}.{stage}``)."""
        return Span(
            self._telemetry.tracer, f"{self.engine}.{name}",
            credit=(self.stage_seconds, name),
        )

    def span(self, name: str, **args) -> Span:
        """A named span inside the call (e.g. ``copy.d2h``) that credits no
        stage: it is a child of whichever stage encloses it."""
        return self._telemetry.tracer.span(name, **args)

    def device_stage(self, variant_key) -> Span:
        """Stage for the jitted device call: ``compile`` on the variant's
        first dispatch in this process, ``execute`` on repeats; also
        bumps the ``jit.variant_hit``/``jit.variant_miss`` counters."""
        hit = variant_seen(variant_key)
        self.count("jit.variant_hit" if hit else "jit.variant_miss")
        return self.stage("execute" if hit else "compile")

    def add_stage(self, name: str, seconds: float):
        """Credit pre-measured seconds to a stage (e.g. the schedule /
        pack timings a :class:`~repro.graph.waves.WaveSchedule` already
        carries from its one ``obs.stopwatch`` timing path)."""
        self.stage_seconds[name] += seconds

    def count(self, name: str, value=1):
        self.counters[name] = self.counters.get(name, 0) + value

    def put(self, name: str, value):
        self.counters[name] = value

    def put_many(self, values: dict, prefix: str = ""):
        for k, v in values.items():
            self.counters[prefix + k] = v

    def block(self, out):
        """``jax.block_until_ready`` so device time lands in the open
        stage — only ever called on the enabled path."""
        import jax

        jax.block_until_ready(out)
        return out

    def finish(self) -> MatchTelemetry:
        wall = time.perf_counter() - self._t0
        record = MatchTelemetry(
            engine=self.engine,
            backend=self.backend,
            interpret=self.interpret,
            num_edges=self.num_edges,
            wall_seconds=wall,
            stage_seconds=dict(self.stage_seconds),
            counters=dict(self.counters),
        )
        tel = self._telemetry
        tel.match_calls.append(record)
        tel.counters.add("substream_match.calls")
        tel.counters.add("jit.variant_hits", self.counters.get("jit.variant_hit", 0))
        tel.counters.add(
            "jit.variant_misses", self.counters.get("jit.variant_miss", 0)
        )
        tel.counters.update(record.counters, prefix=f"{self.engine}.")
        return record


class _NullRecorder:
    """Shared no-op recorder — the entire disabled instrumentation path."""

    __slots__ = ()

    def stage(self, name):
        return NULL_SPAN

    def span(self, name, **args):
        return NULL_SPAN

    def device_stage(self, variant_key):
        # keep the process-wide ledger truthful even when disabled: a
        # warm-up call with telemetry off must count as warm later
        variant_seen(variant_key)
        return NULL_SPAN

    def add_stage(self, name, seconds):
        pass

    def count(self, name, value=1):
        pass

    def put(self, name, value):
        pass

    def put_many(self, values, prefix=""):
        pass

    def block(self, out):
        return out

    def finish(self):
        return None


NULL_RECORDER = _NullRecorder()


def recorder(
    telemetry, engine: str, num_edges: int, backend: str = "", interpret: bool = False
):
    """A :class:`MatchRecorder` when telemetry is enabled, else the
    shared no-op recorder. The single entry engines instrument through."""
    if telemetry is None or not telemetry.enabled:
        return NULL_RECORDER
    return MatchRecorder(telemetry, engine, num_edges, backend, interpret)

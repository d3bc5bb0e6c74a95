"""Part-1 throughput: edges/sec per engine, the repo's perf trajectory.

Compares the five Part-1 engines on Kronecker workloads:

* ``scan``         — the CS-SEQ `lax.scan` oracle (1 edge / step);
* ``pallas_edges`` — the paper-literal Pallas pipeline (1 edge / iter);
* ``pallas_mega``  — the grid-pipelined segment megakernel
  (`schedule="mega"`: scalar-prefetched block-aligned layout,
  ``seg_block`` segments per tile op, double-buffered tile stream);
* ``waves_xla``    — the XLA wave reference (`mwm_waves`);
* ``rounds``       — the propose–accept fixed point (`mwm_rounds`).

Besides the CSV rows every benchmark emits, this one writes
``BENCH_substream.json`` at the repo root — the measured perf record the
acceptance gate reads (mega vs per-edge speedup, mega vs the XLA oracle,
fill, #waves/#segments, scheduler/pack seconds per graph). Every engine
row additionally carries its telemetry block (``stage_seconds`` —
schedule/pack/layout/compile/execute — and the plan/schedule
``counters``), captured by one instrumented cold call + one instrumented
steady call around the disabled-telemetry timed reps; ``--trace out.json``
dumps those instrumented calls as Chrome trace-event JSON for Perfetto.
``--check`` runs :func:`check_report` over the record and exits non-zero
with the violated gates named — never an assert, so CI logs the reason.
The Pallas engines run with ``on_plan_failure="fallback"`` (the guarded
production configuration); each graph also embeds a strict
``validate_stream`` guard record, and the gate requires zero validation
drops and ``fallback.count == 0`` on every Pallas row, so a benchmark
number can never secretly come from a degraded engine. The wave
schedule is built once per graph on the host and its cost reported
separately (it is reusable across L/eps sweeps and engine runs, like the
§4.2 lexicographic pre-sort the paper already assumes); the mega engine
timing still re-pads it block-aligned per call (its own host cost).
Each graph further embeds a ``recovery`` block from the resumable path
(``match_epochs``: producer stall of per-epoch async snapshots relative
to the chunked run without them → ``snapshot_overhead_pct``; a faultline
kill mid-stream + timed cold resume → ``recover_seconds``;
``resumed_bit_exact`` vs a one-shot run; ``clean_retries`` from a
guarded clean run), gated by gate 7.

Scale 14 (n = 16384) covers the VMEM-pressure point where the former
one-wave-one-tile kernel paid O(n·width) whole-block rematerialization
per wave and padded every wave to the hub width (fill ~0.02 there); the
sequential engines are measured with fewer reps at that size to keep the
suite minutes-long.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import make_workload, timed
from repro import obs
from repro.checkpoint import SnapshotManager
from repro.core import ExecutionGuard, mwm_rounds, mwm_scan, validate_stream
from repro.core.matching import mwm_waves
from repro.distributed import StragglerMonitor
from repro.graph.waves import block_aligned_layout, wave_schedule
from repro.kernels.substream_match.ops import (
    MEGA_SEG_BLOCK,
    match_epochs,
    mega_plan,
    substream_match,
    traffic_bytes,
)
from repro.testing import faultline

BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_substream.json"

#: Acceptance gates (checked by --check, e.g. from CI on the scale-10
#: graph): the megakernel must beat per-edge Pallas by this factor in
#: edges/sec, the packed schedule must keep at least this fill, and the
#: megakernel must match or beat the plain-XLA wave oracle (the raised
#: gate of ISSUE 6 — a Pallas pipeline slower than naive XLA is a bug).
TARGET_SPEEDUP = 5.0
TARGET_FILL = 0.5
TARGET_MEGA_VS_XLA = 1.0
#: Gate 7 (ISSUE 9): per-epoch snapshotting may stall the producer by
#: at most this share of the same chunked run without snapshots, the
#: resumed-after-kill result must be bit-exact, and the clean path must
#: log zero retries.
TARGET_SNAPSHOT_OVERHEAD_PCT = 5.0

#: Epoch count of the recovery benchmark (the resumable production
#: configuration: mega engine, fallback cascade, guarded epochs).
RECOVERY_EPOCHS = 4

DEFAULT_SCALES = (10, 12, 14)
EDGE_FACTOR = 8
L = 32
EPS = 0.1

#: Engines that walk one edge per step; above this edge count they get a
#: single timed rep (compile + one steady call) so scale 14 stays
#: benchable.
SEQUENTIAL_ENGINES = ("scan", "pallas_edges")
SEQUENTIAL_REPS_CUTOFF = 50_000


def _instrumented_scan(stream, cfg, telemetry):
    """The scan oracle has no telemetry hook of its own (it is one jitted
    call with no host stages), so the bench instruments it externally."""
    rec = obs.recorder(
        telemetry, "scan", stream.num_edges, jax.default_backend()
    )
    key = ("scan", cfg.n, cfg.L, cfg.eps, stream.num_edges)
    if telemetry.enabled:
        rec.put("stream.num_edges", stream.num_edges)
    with rec.device_stage(key):
        out = mwm_scan(stream, cfg)
        rec.block(out)
    rec.finish()
    return out


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _expected_counters(schedule, cfg, L: int) -> dict:
    """Recompute the plan accounting the mega telemetry counters must
    reproduce bit-exactly — embedded in the report so
    :func:`check_report` can cross-check the emitted counters without
    re-running anything."""
    layout = block_aligned_layout(schedule, MEGA_SEG_BLOCK)
    mplan = mega_plan(cfg.n, L, layout)
    mega_tiles_pad = _round_up(max(layout.num_tiles, 1), mplan.tiles_per_block)
    return {
        "pallas_mega": {
            "plan.gather_bytes": int(mplan.gather_bytes),
            "plan.bit_block_bytes": int(mplan.nbytes),
            "traffic.hbm_bytes": traffic_bytes(
                mega_tiles_pad * mplan.seg_block * mplan.seg, mplan.nbytes
            ),
        },
    }


class _StallMeter:
    """SnapshotManager proxy that times producer-visible snapshot cost.

    ``save()`` is timed — with the async writer this is the host copy
    plus a bounded-queue enqueue, which is exactly the time the epoch
    loop is *blocked* on snapshotting (the stall a device-bound
    producer would also pay). ``wait()`` is a no-op during the timed
    window: the final writer drain is durability cost, not steady-state
    overhead, so it is timed separately (``flush_seconds``) via the
    real manager's ``wait()`` after the timed call returns.
    """

    def __init__(self, inner):
        self._inner = inner
        self.stall_seconds = 0.0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def save(self, state):
        t0 = time.perf_counter()
        self._inner.save(state)
        self.stall_seconds += time.perf_counter() - t0

    def wait(self):
        pass


def _bench_recovery(stream, cfg, schedule, reps: int) -> dict:
    """Measure the resumable path: snapshot overhead, kill, recover.

    Protocol (all runs use the guarded production configuration — mega
    engine, fallback cascade, ``RECOVERY_EPOCHS`` epochs):

    1. one guarded clean run with live telemetry — ``clean_retries``
       must come out 0 (gate 7: the guard never fires on a clean path);
    2. ``reps`` timed chunked runs without snapshots (min) vs ``reps``
       timed chunked runs with per-epoch **async** snapshots, the final
       writer drain excluded and reported as ``flush_seconds``.
       ``snapshot_overhead_pct`` — the gated number — is the producer
       **stall**: the time the epoch loop is blocked inside ``save()``
       (host copy + bounded-queue enqueue; min over reps) as a share of
       the chunked baseline. The end-to-end wall delta is reported
       unguarded as ``chunked_snapshot_seconds``: on this CPU-interpret
       container the background writer competes with the GIL-bound host
       scheduler, so the wall delta overstates what a device-bound
       producer pays — the stall is the honest critical-path metric and
       still catches any regression that puts blocking IO back on the
       producer (a synchronous save or a per-epoch flush explodes it);
    3. a run killed after epoch ``kill_after_epoch`` via the faultline
       injector, then a timed cold resume from the snapshot directory —
       ``recover_seconds`` covers restore + replay of the suffix only;
    4. the resumed result is compared bit-for-bit against a one-shot
       run (``resumed_bit_exact``).
    """
    kw = dict(
        epochs=RECOVERY_EPOCHS, engine="mega", on_plan_failure="fallback"
    )
    # the recovery protocol is cheap (~2s/graph), so even a --reps 1 CI
    # run takes 3 timed reps here: the gated stall is a min-over-reps
    # statistic and a single sample would gate on scheduler noise
    reps = max(reps, 3)

    # 1. clean guarded run: warms every per-epoch jit variant and proves
    # the guard stays silent when nothing is injected
    tel = obs.Telemetry()
    guard = ExecutionGuard(
        retries=2, telemetry=tel, monitor=StragglerMonitor(warmup_steps=1)
    )
    clean = match_epochs(stream, cfg, guard=guard, telemetry=tel, **kw)
    jax.block_until_ready(clean.assigned)
    clean_retries = int(tel.counters.asdict().get("guard.retry", 0))

    # 2. chunked without snapshots vs chunked with async snapshots
    def plain():
        out = match_epochs(stream, cfg, **kw)
        jax.block_until_ready(out.assigned)
        return out

    t_plain, _ = timed(plain, reps=reps, warmup=0)

    snap_times: list[float] = []
    stall_times: list[float] = []
    flush_times: list[float] = []
    for _ in range(reps):
        snapdir = tempfile.mkdtemp(prefix="bench_recovery_")
        try:
            meter = _StallMeter(
                SnapshotManager(snapdir, keep=1, async_save=True)
            )
            t0 = time.perf_counter()
            out = match_epochs(stream, cfg, snapshots=meter, **kw)
            jax.block_until_ready(out.assigned)
            snap_times.append(time.perf_counter() - t0)
            stall_times.append(meter.stall_seconds)
            t0 = time.perf_counter()
            meter._inner.wait()
            flush_times.append(time.perf_counter() - t0)
        finally:
            shutil.rmtree(snapdir, ignore_errors=True)
    t_snap = min(snap_times)
    stall = min(stall_times)
    overhead_pct = stall / t_plain * 100.0

    # 3. kill mid-stream, then time the cold resume (restore + suffix)
    kill_after = RECOVERY_EPOCHS // 2 - 1  # half the stream durable
    snapdir = tempfile.mkdtemp(prefix="bench_recovery_kill_")
    try:
        snaps = SnapshotManager(snapdir, keep=1, async_save=True)
        try:
            match_epochs(
                stream, cfg, snapshots=snaps,
                epoch_hook=faultline.kill_at_epoch(kill_after), **kw
            )
        except faultline.SimulatedCrash:
            pass
        snaps.wait()  # the injector kills the epoch loop, not the writer
        t0 = time.perf_counter()
        resumed = match_epochs(
            stream, cfg,
            snapshots=SnapshotManager(snapdir, keep=1, async_save=True),
            **kw,
        )
        jax.block_until_ready(resumed.assigned)
        recover_seconds = time.perf_counter() - t0
    finally:
        shutil.rmtree(snapdir, ignore_errors=True)

    # 4. bit-exactness of the resumed run against a one-shot run
    oneshot = substream_match(
        stream, cfg, schedule="mega", waves=schedule,
        on_plan_failure="fallback",
    )
    resumed_bit_exact = bool(
        np.array_equal(np.asarray(resumed.assigned), np.asarray(oneshot.assigned))
        and np.array_equal(
            np.asarray(resumed.mb_packed if resumed.is_packed else resumed.mb),
            np.asarray(oneshot.mb_packed if oneshot.is_packed else oneshot.mb),
        )
    )
    return {
        "epochs": RECOVERY_EPOCHS,
        "engine": "mega",
        "chunked_seconds": t_plain,
        "chunked_snapshot_seconds": t_snap,
        "snapshot_stall_seconds": stall,
        "snapshot_overhead_pct": round(overhead_pct, 2),
        "flush_seconds": min(flush_times),
        "kill_after_epoch": kill_after,
        "recover_seconds": recover_seconds,
        "resumed_bit_exact": resumed_bit_exact,
        "clean_retries": clean_retries,
    }


def _bench_graph(
    scale: int, edge_factor: int, L: int, eps: float, reps: int, telemetry
):
    stream, cfg = make_workload(scale, edge_factor, L, eps)
    m = stream.num_edges

    # clean-path guard record: the bench workload must validate strictly
    # (a raise here means the generator regressed), and the report embeds
    # the guard counters so the gate can pin "no drops, no degradation"
    _, vreport = validate_stream(stream, cfg.n, policy="strict", telemetry=telemetry)
    validation = {"policy": vreport.policy, **vreport.counters()}

    schedule = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
        telemetry=telemetry,
    )

    engines = {
        "scan": lambda tel=obs.DISABLED: _instrumented_scan(stream, cfg, tel),
        "pallas_edges": lambda tel=obs.DISABLED: substream_match(
            stream, cfg, schedule="edges", telemetry=tel,
            on_plan_failure="fallback",
        ),
        "pallas_mega": lambda tel=obs.DISABLED: substream_match(
            stream, cfg, schedule="mega", waves=schedule, telemetry=tel,
            on_plan_failure="fallback",
        ),
        "waves_xla": lambda tel=obs.DISABLED: mwm_waves(
            stream, cfg, schedule=schedule, telemetry=tel
        ),
        "rounds": lambda tel=obs.DISABLED: mwm_rounds(
            stream, cfg, telemetry=tel
        ),
    }
    timings = {}
    for name, fn in engines.items():
        r = reps
        seq_single = name in SEQUENTIAL_ENGINES and m > SEQUENTIAL_REPS_CUTOFF
        if seq_single:
            r = 1
        # measurement protocol: one instrumented cold call captures the
        # compile stage (and doubles as the warmup), the timed reps run
        # with telemetry DISABLED (so seconds_per_call stays the raw
        # engine speed), and one instrumented steady call captures the
        # execute/schedule/layout split. Sequential engines over the
        # cutoff reuse the steady instrumented call as their single
        # timed rep (telemetry overhead is noise at that call length).
        fn(telemetry)
        cold = telemetry.match_calls[-1]
        if seq_single:
            fn(telemetry)
            steady = telemetry.match_calls[-1]
            t = steady.wall_seconds
        else:
            t, _ = timed(fn, reps=r, warmup=0)
            fn(telemetry)
            steady = telemetry.match_calls[-1]
        stage_seconds = {
            s: cold.stage_seconds.get(s, 0.0) + steady.stage_seconds.get(s, 0.0)
            for s in obs.STAGES
        }
        timings[name] = {
            "seconds_per_call": t,
            "edges_per_sec": m / t if t > 0 else float("inf"),
            "reps": r,
            "backend": steady.backend,
            "interpret": steady.interpret,
            # stage split summed over the two instrumented calls (cold
            # contributes compile, steady contributes execute; host
            # stages appear in both) — disjoint subintervals, so the
            # stage sum never exceeds telemetry_wall_seconds
            "stage_seconds": stage_seconds,
            "telemetry_wall_seconds": cold.wall_seconds + steady.wall_seconds,
            "counters": {k: steady.counters[k] for k in sorted(steady.counters)},
        }
    speedup = (
        timings["pallas_mega"]["edges_per_sec"]
        / timings["pallas_edges"]["edges_per_sec"]
    )
    mega_vs_xla = (
        timings["pallas_mega"]["edges_per_sec"]
        / timings["waves_xla"]["edges_per_sec"]
    )
    return {
        "scale": scale,
        "n": cfg.n,
        "m": m,
        "L": L,
        "eps": eps,
        "num_waves": schedule.num_waves,
        "num_segments": schedule.num_segments,
        "seg_width": schedule.width,
        "max_wave_size": schedule.max_wave_size,
        "wave_fill": round(schedule.fill, 4),
        "edges_per_wave": round(m / max(schedule.num_waves, 1), 1),
        "schedule_seconds": schedule.schedule_seconds,
        "pack_seconds": schedule.pack_seconds,
        "validation": validation,
        "expected_counters": _expected_counters(schedule, cfg, L),
        "recovery": _bench_recovery(stream, cfg, schedule, reps),
        "engines": timings,
        "speedup_pallas_mega_vs_edges": round(speedup, 2),
        "speedup_mega_vs_xla": round(mega_vs_xla, 2),
    }


def run(scales=DEFAULT_SCALES, edge_factor=EDGE_FACTOR, L=L, eps=EPS, reps=3,
        emit_json=True, path: pathlib.Path | None = None):
    """Benchmark entry (rows for benchmarks.run + JSON side artifact)."""
    rows, _report = run_report(
        scales=scales, edge_factor=edge_factor, L=L, eps=eps, reps=reps,
        emit_json=emit_json, path=path,
    )
    return rows


def run_report(scales=DEFAULT_SCALES, edge_factor=EDGE_FACTOR, L=L, eps=EPS,
               reps=3, emit_json=True, path: pathlib.Path | None = None,
               telemetry=None):
    """Like :func:`run` but also returns the JSON report (for --check).

    ``telemetry`` (default: a fresh :class:`repro.obs.Telemetry`) is the
    session the instrumented cold/steady calls record into; pass your
    own to keep the trace (``--trace`` in :func:`main` does).
    """
    if telemetry is None:
        telemetry = obs.Telemetry()
    graphs = [_bench_graph(s, edge_factor, L, eps, reps, telemetry) for s in scales]
    min_speedup = min(g["speedup_pallas_mega_vs_edges"] for g in graphs)
    min_fill = min(g["wave_fill"] for g in graphs)
    min_mega = min(g["speedup_mega_vs_xla"] for g in graphs)
    max_overhead = max(g["recovery"]["snapshot_overhead_pct"] for g in graphs)
    all_bit_exact = all(g["recovery"]["resumed_bit_exact"] for g in graphs)
    clean_retries = sum(g["recovery"]["clean_retries"] for g in graphs)
    report = {
        "benchmark": "bench_throughput",
        "unit": "edges_per_sec",
        "config": {
            "scales": list(scales),
            "edge_factor": edge_factor,
            "L": L,
            "eps": eps,
            "reps": reps,
        },
        "graphs": graphs,
        "acceptance": {
            "target_speedup_pallas_mega_vs_edges": TARGET_SPEEDUP,
            "measured_min_speedup": min_speedup,
            "target_wave_fill": TARGET_FILL,
            "measured_min_wave_fill": min_fill,
            "target_mega_vs_xla": TARGET_MEGA_VS_XLA,
            "measured_min_mega_vs_xla": min_mega,
            "target_snapshot_overhead_pct": TARGET_SNAPSHOT_OVERHEAD_PCT,
            "measured_max_snapshot_overhead_pct": max_overhead,
            "resumed_bit_exact": all_bit_exact,
            "clean_retries": clean_retries,
            "pass": bool(
                min_speedup >= TARGET_SPEEDUP
                and min_fill >= TARGET_FILL
                and min_mega >= TARGET_MEGA_VS_XLA
                and max_overhead <= TARGET_SNAPSHOT_OVERHEAD_PCT
                and all_bit_exact
                and clean_retries == 0
            ),
        },
    }
    if emit_json:
        out = path or BENCH_PATH
        out.write_text(json.dumps(report, indent=2) + "\n")

    rows = []
    for g in graphs:
        tag = f"throughput_s{g['scale']}"
        for name, t in g["engines"].items():
            rows.append(
                (
                    f"{tag}_{name}",
                    t["seconds_per_call"] * 1e6,
                    f"{t['edges_per_sec']:.3e} edges/s",
                )
            )
        rows.append(
            (
                f"{tag}_waves",
                (g["schedule_seconds"] + g["pack_seconds"]) * 1e6,
                f"{g['num_waves']} waves {g['num_segments']} segs "
                f"fill={g['wave_fill']:.2f} "
                f"speedup={g['speedup_pallas_mega_vs_edges']:.1f}x "
                f"mega_vs_xla={g['speedup_mega_vs_xla']:.2f}x",
            )
        )
    return rows, report


def check_report(report: dict) -> tuple[bool, list[str]]:
    """The --check gate as a pure function: report dict in, verdict out.

    Returns ``(ok, messages)`` where every message names one gate with
    its measured and target values — PASS lines when satisfied, FAIL
    lines when violated. A structurally broken report (missing keys,
    no graphs) fails loudly instead of passing vacuously, so a refactor
    that stops emitting a gate input can never silently disable it.
    Gates, each enforced on EVERY benched graph:

    * ``pallas_mega`` >= ``TARGET_SPEEDUP`` x ``pallas_edges``;
    * wave fill >= ``TARGET_FILL``;
    * ``pallas_mega`` >= ``TARGET_MEGA_VS_XLA`` x ``waves_xla`` (the
      raised ISSUE-6 gate: the megakernel must beat the XLA oracle);
    * every engine row carries a complete, internally consistent
      telemetry block (all five ``stage_seconds`` keys, non-negative,
      summing within ``telemetry_wall_seconds``; a non-empty
      ``counters`` dict) — a refactor that drops the instrumentation
      fails here instead of silently un-observing the bench;
    * the mega counters reproduce the plan accounting embedded in
      ``expected_counters`` **bit-exactly** (gather bytes, bit-block
      bytes, modeled HBM traffic);
    * the clean-path guard: every graph embeds a ``validation`` block
      with zero dropped edges / zero problems, and every Pallas engine
      row carries ``fallback.count == 0`` — the bench numbers must come
      from the engine they are labeled with, never from a silent
      fallback degradation, and a report without the guard record
      fails rather than passing vacuously;
    * the recovery gate (gate 7, ISSUE 9): every graph embeds a
      ``recovery`` block from the resumable path and on it the producer
      stall of per-epoch async snapshotting (time blocked in ``save``)
      is at most ``TARGET_SNAPSHOT_OVERHEAD_PCT`` of the identical
      chunked run without snapshots, the killed-and-resumed result is
      bit-exact against a one-shot run, and the guarded clean run
      logged zero ``guard.retry`` events — a report without the block
      fails rather than passing vacuously.
    """
    msgs: list[str] = []
    graphs = report.get("graphs")
    if not graphs:
        return False, ["FAIL report has no graphs (nothing was benched)"]
    ok = True
    gates = (
        ("speedup_pallas_mega_vs_edges", TARGET_SPEEDUP,
         "pallas_mega vs pallas_edges speedup"),
        ("wave_fill", TARGET_FILL, "wave fill"),
        ("speedup_mega_vs_xla", TARGET_MEGA_VS_XLA,
         "pallas_mega vs waves_xla speedup"),
    )
    for key, target, label in gates:
        missing = [g.get("scale", "?") for g in graphs if key not in g]
        if missing:
            ok = False
            msgs.append(f"FAIL {label}: key {key!r} missing at scales {missing}")
            continue
        worst = min(graphs, key=lambda g: g[key])
        verdict = worst[key] >= target
        ok = ok and verdict
        msgs.append(
            f"{'PASS' if verdict else 'FAIL'} {label}: min {worst[key]:.3g} "
            f"at scale {worst.get('scale', '?')} (target >= {target})"
        )

    # telemetry structure + internal consistency, every engine row
    problems: list[str] = []
    for g in graphs:
        scale = g.get("scale", "?")
        for name, row in g.get("engines", {}).items():
            where = f"scale {scale} engine {name}"
            stages = row.get("stage_seconds")
            if stages is None:
                problems.append(f"{where}: no stage_seconds")
                continue
            wall = row.get("telemetry_wall_seconds")
            if wall is None:
                problems.append(f"{where}: no telemetry_wall_seconds")
                continue
            problems.extend(
                f"{where}: {p}"
                for p in obs.consistency_problems(stages, wall)
            )
            if not row.get("counters"):
                problems.append(f"{where}: no counters")
    verdict = not problems
    ok = ok and verdict
    msgs.append(
        f"{'PASS' if verdict else 'FAIL'} telemetry stage_seconds/counters "
        f"on every engine row"
        + ("" if verdict else ": " + "; ".join(problems))
    )

    # plan-counter accounting: the emitted mega counters must equal
    # the independently recomputed plan accounting bit-exactly
    mismatches: list[str] = []
    for g in graphs:
        scale = g.get("scale", "?")
        expected = g.get("expected_counters")
        if not expected:
            mismatches.append(f"scale {scale}: no expected_counters in report")
            continue
        for name, want in expected.items():
            got = g.get("engines", {}).get(name, {}).get("counters", {})
            for key, val in want.items():
                if key not in got:
                    mismatches.append(
                        f"scale {scale} engine {name}: counter {key!r} missing"
                    )
                elif got[key] != val:
                    mismatches.append(
                        f"scale {scale} engine {name}: {key} = {got[key]} "
                        f"!= expected {val}"
                    )
    verdict = not mismatches
    ok = ok and verdict
    msgs.append(
        f"{'PASS' if verdict else 'FAIL'} plan-counter accounting "
        f"(gather/bit-block/traffic bytes bit-exact)"
        + ("" if verdict else ": " + "; ".join(mismatches))
    )

    # clean-path guard: the bench input validated clean and no Pallas
    # engine silently degraded down the fallback cascade
    guard_problems: list[str] = []
    for g in graphs:
        scale = g.get("scale", "?")
        v = g.get("validation")
        if not v:
            guard_problems.append(f"scale {scale}: no validation block")
        else:
            for key in ("guard.dropped_edges", "guard.num_problems"):
                if v.get(key) != 0:
                    guard_problems.append(
                        f"scale {scale}: {key} = {v.get(key, 'missing')} "
                        f"on the clean bench path"
                    )
        for name, row in g.get("engines", {}).items():
            if not name.startswith("pallas_"):
                continue
            fb = row.get("counters", {}).get("fallback.count")
            if fb is None:
                guard_problems.append(
                    f"scale {scale} engine {name}: no fallback.count counter"
                )
            elif fb != 0:
                guard_problems.append(
                    f"scale {scale} engine {name}: fallback.count = {fb} "
                    f"(engine silently degraded)"
                )
    verdict = not guard_problems
    ok = ok and verdict
    msgs.append(
        f"{'PASS' if verdict else 'FAIL'} clean-path guard "
        f"(validation clean, fallback.count == 0 on every Pallas row)"
        + ("" if verdict else ": " + "; ".join(guard_problems))
    )

    # gate 7: the resumable path — per-epoch snapshotting within budget,
    # the killed-and-resumed result bit-exact, no retries on a clean run
    recovery_problems: list[str] = []
    for g in graphs:
        scale = g.get("scale", "?")
        rec = g.get("recovery")
        if not rec:
            recovery_problems.append(f"scale {scale}: no recovery block")
            continue
        pct = rec.get("snapshot_overhead_pct")
        if pct is None:
            recovery_problems.append(
                f"scale {scale}: no snapshot_overhead_pct"
            )
        elif pct > TARGET_SNAPSHOT_OVERHEAD_PCT:
            recovery_problems.append(
                f"scale {scale}: snapshot overhead {pct:.2f}% "
                f"(target <= {TARGET_SNAPSHOT_OVERHEAD_PCT}%)"
            )
        if rec.get("resumed_bit_exact") is not True:
            recovery_problems.append(
                f"scale {scale}: resumed result not bit-exact vs one-shot"
            )
        if rec.get("clean_retries") != 0:
            recovery_problems.append(
                f"scale {scale}: clean_retries = "
                f"{rec.get('clean_retries', 'missing')} (guard fired on a "
                f"clean path)"
            )
    verdict = not recovery_problems
    ok = ok and verdict
    msgs.append(
        f"{'PASS' if verdict else 'FAIL'} recovery gate (snapshot overhead "
        f"<= {TARGET_SNAPSHOT_OVERHEAD_PCT}%, resumed bit-exact, zero "
        f"clean-path retries)"
        + ("" if verdict else ": " + "; ".join(recovery_problems))
    )
    return ok, msgs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scales", type=int, nargs="+", default=list(DEFAULT_SCALES))
    ap.add_argument("--edge-factor", type=int, default=EDGE_FACTOR)
    ap.add_argument("--L", type=int, default=L)
    ap.add_argument("--eps", type=float, default=EPS)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero unless on every benched graph wave_fill >= "
        "%.2f, mega-vs-edge speedup >= %.1f, mega >= %.1fx waves_xla, "
        "every engine row carries consistent telemetry, the input "
        "validated clean, no Pallas engine fell back, and the recovery "
        "block shows snapshot overhead <= %.1f%%, a bit-exact resume, "
        "and zero clean-path retries"
        % (TARGET_FILL, TARGET_SPEEDUP, TARGET_MEGA_VS_XLA,
           TARGET_SNAPSHOT_OVERHEAD_PCT),
    )
    ap.add_argument(
        "--trace",
        metavar="OUT_JSON",
        help="write the Chrome trace-event JSON of the instrumented "
        "bench calls here (open in ui.perfetto.dev)",
    )
    args = ap.parse_args()
    telemetry = obs.Telemetry()
    rows, report = run_report(
        scales=tuple(args.scales),
        edge_factor=args.edge_factor,
        L=args.L,
        eps=args.eps,
        reps=args.reps,
        emit_json=not args.no_json,
        telemetry=telemetry,
    )
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    if not args.no_json:
        print(f"# wrote {BENCH_PATH}")
    if args.trace:
        telemetry.write_chrome_trace(args.trace)
        print(f"# wrote {args.trace}")
    if args.check:
        ok, msgs = check_report(report)
        for msg in msgs:
            print(f"# gate: {msg}")
        if not ok:
            sys.exit("bench gate FAILED (see gate lines above)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

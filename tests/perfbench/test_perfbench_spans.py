"""The program's spans as the benchmark reads them (``perfbench.spans``
and the per-layer metrics built on it), on the CPU."""
from __future__ import annotations

import importlib
import types

import numpy as np
import pytest

from perfbench import harness, spans, trace


def _timeline():
    return trace.Timeline(
        device_ops={"/device:TPU:0": [("fusion", 10, 20, ""), ("substream_match", 60, 70, "")]},
        marks=[("pb.window", 0, 100), ("pb.job", 0, 100), ("pb.engine", 5, 75),
               ("pb.merge", 75, 100)],
    )


def test_a_gap_goes_to_the_innermost_annotation_of_either_kind():
    program = [
        ("repro.wave_schedule.assign", 20, 50),
        ("repro.copy.d2h", 30, 40),  # inside the assignment
        ("repro.merge.host", 80, 95),
    ]
    got = dict(spans.idle_by_stage(_timeline(), program))
    # idle [0, 10]: job to 5, engine after; [20, 60]: the assignment
    # around its copy, the engine after it; [70, 100]: engine, then the
    # merge mark around the program's merge span
    assert got == {
        "repro.wave_schedule.assign": pytest.approx(20e-9),
        "repro.copy.d2h": pytest.approx(10e-9),
        "engine": pytest.approx(20e-9),
        "job": pytest.approx(5e-9),
        "repro.merge.host": pytest.approx(15e-9),
        "merge": pytest.approx(10e-9),
    }
    assert sum(got.values()) == pytest.approx(80e-9)  # the window less 20 busy


def test_an_old_timeline_without_program_spans_reads_as_before():
    """A timeline written before the program had spans (device ops and
    benchmark marks only) loads, reduces to the same numbers, and its
    idle time goes to benchmark marks alone."""
    old = (
        '{"device_ops":{"/device:TPU:0":[["fusion",10,20,""],["substream_match",60,70,""]]},'
        '"marks":[["pb.window",0,100],["pb.job",0,100],["pb.engine",5,75],["pb.merge",75,100]]}'
    )
    tl = trace.Timeline.from_json(old)
    assert tl == _timeline()
    r = trace.reduce(tl)
    assert (r.window_s, r.busy_s) == (pytest.approx(100e-9), pytest.approx(20e-9))
    assert r.seconds_of("substream_match") == pytest.approx(10e-9)
    assert dict(r.idle_gaps) == {"engine": pytest.approx(50e-9), "merge": pytest.approx(30e-9)}
    got = dict(spans.idle_by_stage(tl, []))
    assert set(got) == {"job", "engine", "merge"}
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_s)


def test_program_spans_are_read_from_a_real_profile(tmp_path):
    """A traced interpret-mode mega job: the loader finds the program's
    ``repro.*`` annotations in the profile, inside the benchmark's
    window, and the copies inside the layout stage."""
    import jax

    from repro import obs
    from repro.core import EdgeStream, SubstreamConfig
    from repro.kernels.substream_match.ops import substream_match

    rng = np.random.default_rng(3)
    stream = EdgeStream.from_numpy(
        rng.integers(0, 64, 300).astype(np.int32), rng.integers(0, 64, 300).astype(np.int32),
        (rng.random(300) * 10 + 1).astype(np.float32),
    )
    cfg = SubstreamConfig(n=64, L=8, eps=0.1)
    substream_match(stream, cfg, schedule="mega")  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("pb.window"):
            substream_match(stream, cfg, schedule="mega", telemetry=obs.Telemetry())
    finally:
        jax.profiler.stop_trace()
    program = spans.load(tmp_path)
    names = {name for name, _, _ in program}
    assert {"repro.wave_schedule.assign", "repro.wave_schedule.pack", "repro.layout.gather",
            "repro.layout.scatter", "repro.copy.d2h", "repro.copy.h2d"} <= names
    seconds = spans.host_seconds(trace.load(tmp_path), program)
    assert seconds["repro.copy.d2h in repro.pallas_mega.layout"] > 0
    assert seconds["repro.copy.d2h"] > seconds["repro.copy.d2h in repro.pallas_mega.layout"]


def _session(events, trips):
    tracer = types.SimpleNamespace(events=[
        {"name": name, "ph": "X", "dur": dur, "args": {}} for name, dur in events
    ])
    calls = [types.SimpleNamespace(counters={} if t is None else {"kernel.trips": t})
             for t in trips]
    return types.SimpleNamespace(tracer=tracer, match_calls=calls)


#: Two mega jobs of a telemetry session (durations in us, as recorded).
MEGA = [
    ("wave_schedule.assign", 1_500_000.0), ("wave_schedule.assign", 1_700_000.0),
    ("wave_schedule.pack", 300_000.0), ("wave_schedule.pack", 250_000.0),
    ("layout.block_align", 40_000.0), ("layout.gather", 100_000.0),
    ("layout.scatter", 60_000.0), ("layout.block_align", 40_000.0),
    ("layout.gather", 100_000.0), ("layout.scatter", 60_000.0),
    ("copy.d2h", 10_000.0), ("copy.d2h", 6_000.0), ("copy.h2d", 4_000.0),
    ("pallas_mega.layout", 420_000.0), ("merge.host", 25_000.0),
]


@pytest.mark.parametrize("metric,events,trips,kernel_s,want", [
    ("schedule_assign_ms", MEGA, [160_000, 160_000], 0.2, 1600.0),
    ("schedule_pack_ms", MEGA, [160_000, 160_000], 0.2, 275.0),
    ("slot_layout_ms", MEGA, [160_000, 160_000], 0.2, 200.0),
    ("host_copy_ms", MEGA, [160_000, 160_000], 0.2, 10.0),
    ("kernel_trip_us", MEGA, [160_000, 160_000], 0.2, 0.625),
    # a per-edge job: no schedule or layout, copies in the merge
    ("schedule_assign_ms", [("copy.d2h", 30_000.0)], [2_431_631], 0.7, None),
    ("slot_layout_ms", [("copy.d2h", 30_000.0)], [2_431_631], 0.7, None),
    ("host_copy_ms", [("copy.d2h", 30_000.0)], [2_431_631], 0.7, 15.0),
    ("kernel_trip_us", [], [2_431_631, 2_431_631], 0.7, 0.7 / 4_863_262 * 1e6),
    # a program that records no copy span and counts no trips
    ("host_copy_ms", [("merge.host", 25_000.0)], [None], 0.2, None),
    ("kernel_trip_us", [], [None, None], 0.2, None),
    # trips but no kernel in the device trace
    ("kernel_trip_us", [], [100], None, None),
])
def test_program_span_metric_readers(metric, events, trips, kernel_s, want):
    ctx = harness.LayerContext(
        trace=types.SimpleNamespace(seconds_of=lambda text: kernel_s),
        telemetry=_session(events, trips), jobs=2, workload=None, peak=None,
    )
    got = importlib.import_module(f"perfbench.metrics.{metric}").read(ctx)
    assert got == (None if want is None else pytest.approx(want))

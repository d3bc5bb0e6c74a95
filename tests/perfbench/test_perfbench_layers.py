"""The benchmark's generators, roofline count, trace reduction and
per-layer metric readers, on the CPU."""
from __future__ import annotations

import importlib
import types

import numpy as np
import pytest

from perfbench import harness, roofline, trace
from perfbench.graphs import delaunay, kronecker

def test_kronecker_is_fixed_by_its_seed():
    p = {"scale": 8, "n": 256, "edge_factor": 8, "initiator": [0.57, 0.19, 0.19, 0.05], "seed": 3}
    a, b = kronecker.generate(p), kronecker.generate(p)
    c = kronecker.generate(dict(p, seed=4))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:100], c[0][:100])
    src, dst = a
    assert (src != dst).all()
    key = np.minimum(src, dst).astype(np.int64) * 256 + np.maximum(src, dst)
    assert np.unique(key).size == key.size


def test_kronecker_copy_matches_the_program_generator():
    from repro.graph.generators import kronecker_graph

    p = {"scale": 9, "n": 512, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05], "seed": 0}
    src, dst = kronecker.generate(p)
    want_src, want_dst = kronecker_graph(9, 16, seed=0)
    np.testing.assert_array_equal(src, want_src)
    np.testing.assert_array_equal(dst, want_dst)


def test_delaunay_is_a_planar_triangulation_fixed_by_its_seed():
    p = {"n": 4096, "seed": 5}
    src, dst = delaunay.generate(p)
    again = delaunay.generate(p)
    np.testing.assert_array_equal(src, again[0])
    m = src.size
    # a triangulation of n points in general position has 3n - 3 - h
    # edges, h the points on the hull (a few dozen here)
    assert 3 * 4096 - 3 - 200 <= m <= 3 * 4096 - 6
    assert (src < dst).all() and dst.max() < 4096


def test_problem_bytes_are_the_same_for_every_engine():
    """The roofline's work depends on the stream, not the path: the
    reader gives the same share for an edges job and a mega job of equal
    kernel time on one stream."""
    wl = types.SimpleNamespace(m=2_431_631, n=65_536, L=64)
    assert roofline.problem_bytes(wl.m, wl.n, wl.L) == 16 * 2_431_631 + 8 * 65_536
    reader = importlib.import_module("perfbench.metrics.substream_match_roofline")
    peak = roofline.peaks("TPU v5 lite")
    shares = []
    for op in ("substream_match", "substream_match.1"):  # edges / mega calls
        t = trace.Reduced(
            window_s=2.0, busy_s=1.1, devices=1, op_seconds={}, op_counts={},
            device_ops=[], idle_gaps=[], ops=[(op, "", 0.5), ("fusion", "", 0.1)],
        )
        ctx = harness.LayerContext(trace=t, telemetry=None, jobs=2, workload=wl, peak=peak)
        shares.append(reader.read(ctx))
    assert shares[0] == shares[1]
    want = 100 * roofline.problem_bytes(wl.m, wl.n, wl.L) / 819e9 / 0.25
    assert shares[0] == pytest.approx(want)


def test_a_device_kind_missing_from_the_peak_table_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        roofline.peaks("TPU v99")


def _synthetic():
    """One device, window [0, 100]: ops at [10, 20] (the kernel), [15, 30]
    (an overlapping copy), [60, 70] (the kernel, named only by its
    framework op); the host in a job over [0, 100] with an engine call
    over [5, 75] and a merge over [75, 100]."""
    return trace.Timeline(
        device_ops={"/device:TPU:0": [
            ("substream_match", 10, 20, ""), ("copy", 15, 30, ""),
            ("custom-call.3", 60, 70, "jit(f)/substream_match"),
            ("fusion", 120, 130, ""),  # outside the window
        ]},
        marks=[("pb.window", 0, 100), ("pb.job", 0, 100), ("pb.engine", 5, 75),
               ("pb.merge", 75, 100)],
    )


def test_reduce_on_a_synthetic_timeline():
    r = trace.reduce(_synthetic())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(30e-9)  # [10, 30] and [60, 70]
    assert r.op_counts == {"substream_match": 1, "copy": 1, "custom-call.3": 1}
    assert r.device_ops[0] == ["copy", pytest.approx(15e-9)]
    # the kernel by its event name or by its framework name
    assert r.seconds_of("substream_match") == pytest.approx(20e-9)
    assert r.seconds_of("no_such_kernel") is None
    # idle [0, 10] and [30, 60] fall in the engine call, [70, 100] in
    # the merge
    gaps = dict(r.idle_gaps)
    assert gaps["engine"] == pytest.approx(40e-9)
    assert gaps["merge"] == pytest.approx(30e-9)


@pytest.mark.parametrize("metric,want", [
    ("kernel_ms", 20e-9 / 2 * 1e3),  # two jobs
    ("device_idle_pct", 70.0),
    ("substream_match_roofline", 100 * (16 * 100 + 8 * 64) / 819e9 / 10e-9),  # 25.8 %
])
def test_trace_readers_on_a_synthetic_timeline(metric, want):
    wl = types.SimpleNamespace(m=100, n=64, L=64)
    ctx = harness.LayerContext(
        trace=trace.reduce(_synthetic()), telemetry=None, jobs=2, workload=wl,
        peak=roofline.peaks("TPU v5 lite"),
    )
    got = importlib.import_module(f"perfbench.metrics.{metric}").read(ctx)
    assert got == pytest.approx(want)


def test_timeline_json_round_trip():
    tl = _synthetic()
    again = trace.Timeline.from_json(tl.to_json())
    assert again == tl


def _telemetry(stages, merges):
    tracer = types.SimpleNamespace(events=[
        {"name": "merge.host", "ph": "X", "dur": d} for d in merges
    ])
    calls = [types.SimpleNamespace(stage_seconds=s) for s in stages]
    return types.SimpleNamespace(match_calls=calls, tracer=tracer)


@pytest.mark.parametrize("stages,merges,schedule_ms,merge_ms", [
    # two mega jobs: schedule 1.0 + pack 0.2 + layout 0.1 s each, merges of 50 ms
    ([{"schedule": 1.0, "pack": 0.2, "layout": 0.1, "execute": 0.3}] * 2,
     [50_000.0, 50_000.0], 1300.0, 50.0),
    # two per-edge jobs: no schedule, the merge without telemetry
    ([{"schedule": 0.0, "pack": 0.0, "layout": 0.0, "execute": 0.8}] * 2, [], None, None),
])
def test_program_span_readers(stages, merges, schedule_ms, merge_ms):
    tel = _telemetry(stages, merges)
    ctx = harness.LayerContext(trace=None, telemetry=tel, jobs=2, workload=None, peak=None)
    got_schedule = importlib.import_module("perfbench.metrics.host_schedule_ms").read(ctx)
    got_merge = importlib.import_module("perfbench.metrics.merge_ms").read(ctx)
    assert got_schedule == (None if schedule_ms is None else pytest.approx(schedule_ms))
    assert got_merge == (None if merge_ms is None else pytest.approx(merge_ms))

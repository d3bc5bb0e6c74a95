"""The kron20 configuration and the readers of the metrics that its cell
added, on the CPU: the configuration is kron16's at the published scale,
the readers read the program's cast and greedy-pass spans, and the
spans change no answer."""
from __future__ import annotations

import importlib
import json
import pathlib
import types

import numpy as np
import pytest

from perfbench import harness, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = harness.load_spec(ROOT)


def _config(name):
    entry = {c["name"]: c for c in SPEC["configs"]}[name]
    return entry, json.loads((ROOT / entry["file"]).read_text())


def test_kron20_is_kron16_at_the_published_scale():
    entry, k20 = _config("kron20")
    _, k16 = _config("kron16")
    assert entry["reduced"] == [] and k20["reduced"] == {}
    assert k20["scale"] == k20["graph"]["scale"] == 20
    assert k20["n"] == k20["graph"]["n"] == 2 ** k20["scale"]
    for key in ("initiator", "edge_factor", "family", "seed"):
        assert k20["graph"][key] == k16["graph"][key], key
    differ = {k for k in k16 if k16[k] != k20.get(k)} | (k20.keys() - k16.keys())
    # name, prose and the reason for kron16's cut aside, only the size moves
    assert differ == {"name", "deployment", "assumed", "reduced", "graph", "scale", "n"}
    graph_differ = {k for k in k16["graph"] if k16["graph"][k] != k20["graph"][k]}
    assert graph_differ == {"scale", "n"}
    cells = [c for c in SPEC["workloads"] if c["config"] == "kron20"]
    assert [(c["name"], c["traffic"], c["chips"]) for c in cells] == [
        ("kron20.mega", "mega", 1)
    ]


def _session(events):
    tracer = types.SimpleNamespace(events=[
        {"name": name, "ph": "X", "dur": dur, "args": {}} for name, dur in events
    ])
    return types.SimpleNamespace(tracer=tracer, match_calls=[])


#: Two mega jobs of a telemetry session (durations in us, as recorded).
JOBS = [
    ("wave_schedule.prepare", 900_000.0), ("wave_schedule.assign", 9_000_000.0),
    ("wave_schedule.pack", 2_000_000.0), ("wave_schedule.emit", 400_000.0),
    ("merge.host", 1_500_000.0), ("merge.order", 300_000.0), ("merge.greedy", 1_100_000.0),
    ("wave_schedule.prepare", 700_000.0), ("wave_schedule.assign", 9_500_000.0),
    ("wave_schedule.pack", 2_100_000.0), ("wave_schedule.emit", 600_000.0),
    ("merge.host", 1_300_000.0), ("merge.order", 250_000.0), ("merge.greedy", 900_000.0),
]
#: What a program without the new spans records.
OLDER = [(n, d) for n, d in JOBS if n.split(".")[1] not in ("prepare", "emit", "order", "greedy")]


@pytest.mark.parametrize("metric,events,want", [
    ("schedule_casts_ms", JOBS, 1300.0),
    ("merge_greedy_ms", JOBS, 1000.0),
    ("schedule_casts_ms", OLDER, None),
    ("merge_greedy_ms", OLDER, None),
    ("schedule_casts_ms", [], None),
    ("merge_greedy_ms", [("merge.greedy", 5.0)], 0.0025),
])
def test_new_span_readers(metric, events, want):
    ctx = harness.LayerContext(
        trace=None, telemetry=_session(events), jobs=2, workload=None, peak=None,
    )
    got = importlib.import_module(f"perfbench.metrics.{metric}").read(ctx)
    assert got == (None if want is None else pytest.approx(want))


def test_new_metrics_are_declared_for_the_mega_cells():
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name, layer in (("schedule_casts_ms", "host scheduling and slot layout"),
                        ("merge_greedy_ms", "merge")):
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "edges_per_s")
        assert m["layer"] == layer
        assert m["workloads"] == ["kron20.mega", "kron16.mega", "delaunay20.mega"]


def test_kron16_schedule_and_merge_are_the_same_with_telemetry_on():
    """On the whole kron16 stream: the wave schedule and the merge of
    the reference's Part 1 are bit-identical with telemetry on and off."""
    from repro import obs
    from repro.core import EdgeStream, MatchingResult, SubstreamConfig, merge_host
    from repro.graph.waves import wave_schedule

    _, config = _config("kron16")
    wl = harness.make_workload(config, 2**31 + 777, cache=None)
    tel = obs.Telemetry()
    off = wave_schedule(wl.src, wl.dst)
    on = wave_schedule(wl.src, wl.dst, telemetry=tel)
    for field in ("wave", "order", "offsets", "slots", "seg_offsets"):
        a, b = getattr(off, field), getattr(on, field)
        assert a.dtype == b.dtype == np.int32 and np.array_equal(a, b), field
    cnt = reference.admit_counts(wl.src, wl.dst, wl.weight, wl.L, wl.eps)
    assigned, state = reference.part1(wl.src, wl.dst, cnt, wl.n, wl.L)
    stream = EdgeStream.from_numpy(wl.src, wl.dst, wl.weight)
    res = MatchingResult(assigned=assigned, mb_packed=state, L=wl.L)
    cfg = SubstreamConfig(n=wl.n, L=wl.L, eps=wl.eps)
    merged = merge_host(stream, res, cfg, telemetry=tel)
    np.testing.assert_array_equal(merged, merge_host(stream, res, cfg))
    np.testing.assert_array_equal(merged, reference.part2(wl.src, wl.dst, assigned, wl.n))
    names = {e["name"] for e in tel.tracer.events}
    assert {"wave_schedule.prepare", "wave_schedule.emit", "merge.order",
            "merge.greedy"} <= names


def test_tiny_kron20_job_is_the_same_with_telemetry_on():
    """A whole kron20.mega job on a tiny graph of the family: the same
    answer with the program's telemetry on as with it off."""
    from repro import obs
    from repro.core import SubstreamConfig
    from perfbench.entries import substream_match as entry

    _, config = _config("kron20")
    config = dict(config, graph=dict(config["graph"], scale=8, n=256, edge_factor=8))
    wl = harness.make_workload(config, 2**31 + 99, cache=None)
    wl.cfg = SubstreamConfig(n=wl.n, L=wl.L, eps=wl.eps)
    traffic = json.loads((ROOT / "perfbench" / "traffic" / "mega.json").read_text())
    answers = [
        harness._host_answer(entry.job(wl, traffic, harness.JobContext(tel, annotate=False)))
        for tel in (obs.DISABLED, obs.Telemetry())
    ]
    assert harness._same(*answers)
    assert reference.compare(answers[0], reference.solve(
        wl.src, wl.dst, wl.weight, wl.n, wl.L, wl.eps)) == {
        "assigned_diff": 0, "state_diff": 0, "merged_diff": 0, "weight_gap": 0.0}

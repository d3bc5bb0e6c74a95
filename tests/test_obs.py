"""Tests for the telemetry subsystem (`repro.obs`).

Three invariant families:

* **tracer/schema** — spans nest by interval containment and the export
  is valid Chrome trace-event JSON (complete events with μs ts/dur,
  instant events with scope), so Perfetto opens it;
* **zero-overhead disabled path** — `obs.DISABLED` hands out the same
  shared no-op objects by identity and the hot loop neither records nor
  accumulates allocations;
* **record consistency** — per-engine stage seconds are disjoint
  subintervals of the call wall time, the counters are bit-exact copies
  of the WavePlan / mega_plan / WaveSchedule accounting, and repeated
  runs produce identical counters (modulo the jit hit/miss labels,
  which legitimately flip between a cold and a warm call).
"""
import contextlib
import json
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import merge, mwm_pipeline, rounds
from repro.core.matching import mwm_waves
from repro.core.types import EdgeStream, SubstreamConfig
from repro.graph.waves import (
    block_aligned_layout,
    schedule_counters,
    wave_schedule,
)
from repro.kernels.substream_match.ops import (
    MEGA_SEG_BLOCK,
    match_epochs,
    mega_plan,
    substream_match,
    traffic_bytes,
)
from repro.obs import trace as obs_trace


def _round_up(x, mult):
    return ((x + mult - 1) // mult) * mult


#: The Pallas engines by id: the per-edge kernel, and the megakernel at
#: one segment per tile and at the default ``MEGA_SEG_BLOCK``.
PALLAS = {
    "edges": {"schedule": "edges"},
    "mega_seg1": {"schedule": "mega", "seg_block": 1},
    "mega": {"schedule": "mega"},
}


def _workload(m=600, n=128, L=8, eps=0.1, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = (rng.random(m) * 10 + 1).astype(np.float32)
    stream = EdgeStream.from_numpy(src, dst, w)
    return stream, SubstreamConfig(n=n, L=L, eps=eps)


# ---------------------------------------------------------------- tracer


def test_spans_nest_by_interval_containment():
    tel = obs.Telemetry()
    with tel.span("outer"):
        with tel.span("inner"):
            time.sleep(0.001)
    evs = tel.chrome_trace()["traceEvents"]
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    # inner exits first, so its [ts, ts+dur] sits inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["dur"] >= 1000  # slept 1ms; ts/dur are microseconds


def test_chrome_trace_schema_is_valid():
    tel = obs.Telemetry()
    with tel.span("a", detail=1):
        pass
    tel.event("mark", backend="cpu")
    tel.count("some.counter", 3)
    trace = tel.chrome_trace()
    # round-trips through JSON (what write_chrome_trace emits)
    trace = json.loads(json.dumps(trace))
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["counters"] == {"some.counter": 3}
    for e in trace["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    assert {e["ph"] for e in trace["traceEvents"]} == {"X", "i"}


def test_write_chrome_trace_roundtrip(tmp_path):
    tel = obs.Telemetry()
    with tel.span("s"):
        pass
    path = tmp_path / "trace.json"
    tel.write_chrome_trace(path)
    trace = json.loads(path.read_text())
    assert [e["name"] for e in trace["traceEvents"]] == ["s"]


def test_stopwatch_measures_even_when_disabled():
    with obs.stopwatch(obs.DISABLED, "x") as sw:
        time.sleep(0.001)
    assert sw.seconds >= 0.001
    tel = obs.Telemetry()
    with obs.stopwatch(tel, "x") as sw2:
        pass
    ev = tel.chrome_trace()["traceEvents"][0]
    assert ev["name"] == "x"
    assert ev["dur"] == pytest.approx(sw2.seconds * 1e6, rel=1e-9)


def _spans(tel, name=None):
    return [
        e for e in tel.tracer.events
        if e["ph"] == "X" and (name is None or e["name"] == name)
    ]


def test_enabled_spans_land_in_the_profiler_host_plane(tmp_path):
    """Every recorded span is also a ``repro.<name>`` profiler
    annotation, and the stage seconds are still the spans' own
    measurements beside it."""
    from jax.profiler import ProfileData

    stream, cfg = _workload(m=300, n=64, L=8)
    substream_match(stream, cfg, schedule="mega")  # compile outside the trace
    tel = obs.Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        substream_match(stream, cfg, schedule="mega", telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {
        ev.name for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines for ev in line.events
    }
    assert {
        "repro.wave_schedule.assign", "repro.copy.d2h", "repro.layout.gather",
        "repro.pallas_mega.layout", "repro.copy.h2d",
    } <= names
    rec = tel.match_calls[-1]
    (assign,) = _spans(tel, "wave_schedule.assign")
    layout = sum(e["dur"] for e in _spans(tel, "pallas_mega.layout"))
    assert rec.stage_seconds["schedule"] * 1e6 == pytest.approx(assign["dur"], rel=1e-9)
    assert rec.stage_seconds["layout"] * 1e6 == pytest.approx(layout, rel=1e-9)


def test_span_args_carry_call_and_parent():
    """``call`` ties a job's spans (its merge too) to its engine call;
    ``parent`` names the enclosing span."""
    stream, cfg = _workload(m=300, n=64, L=8)
    tel = obs.Telemetry()
    with tel.span("before", k=1):
        pass
    for _ in range(2):
        res = substream_match(stream, cfg, schedule="mega", telemetry=tel)
        merge.merge_host(stream, res, cfg, telemetry=tel)
    assert _spans(tel, "before")[0]["args"] == {"k": 1, "call": None, "parent": None}
    gathers = _spans(tel, "layout.gather")
    assert [e["args"] for e in gathers] == [
        {"call": 0, "parent": "pallas_mega.layout"},
        {"call": 1, "parent": "pallas_mega.layout"},
    ]
    merge_copies = [
        e["args"] for e in _spans(tel, "copy.d2h") if e["args"]["parent"] == "merge.host"
    ]
    assert [(a["call"], a["what"]) for a in merge_copies] == [
        (0, "stream"), (0, "assigned"), (1, "stream"), (1, "assigned"),
    ]
    d2h = {(e["args"]["what"], e["args"]["parent"]) for e in _spans(tel, "copy.d2h")}
    assert {
        ("links", "wave_schedule.links"), ("stream", "pallas_mega.layout"),
        ("assigned_slots", "pallas_mega.layout"),
    } <= d2h
    # the schedule reads the stream on the device
    assert ("stream", None) not in d2h
    for e in _spans(tel, "copy.h2d"):
        assert e["args"]["parent"] in (
            "pallas_mega.compile", "pallas_mega.execute", None,
        )
    assert {e["args"]["what"] for e in _spans(tel, "copy.h2d")} == {"slots", "assigned"}
    (align,) = [e for e in _spans(tel, "layout.block_align") if e["args"]["call"] == 1]
    assert align["args"]["parent"] == "pallas_mega.layout"


@pytest.mark.parametrize("eng", list(PALLAS))
def test_kernel_trips_are_the_plans_trip_count(eng):
    """``kernel.trips`` is the kernel's dependent trip count, exact from
    the plan: one per edge, per one-segment tile, or per mega tile."""
    stream, cfg = _workload(m=700, n=160, L=8)
    src, dst = np.asarray(stream.src), np.asarray(stream.dst)
    tel = obs.Telemetry()
    substream_match(stream, cfg, telemetry=tel, **PALLAS[eng])
    sch = wave_schedule(src, dst, valid=np.asarray(stream.valid))
    want = {
        "edges": lambda: stream.num_edges,
        "mega_seg1": lambda: sch.num_segments,
        "mega": lambda: mega_plan(
            cfg.n, cfg.L, block_aligned_layout(sch, MEGA_SEG_BLOCK)
        ).num_tiles,
    }[eng]()
    assert tel.match_calls[-1].counters["kernel.trips"] == want


def test_disabled_path_opens_no_profiler_annotation(monkeypatch):
    opened = []

    def annotation(name):
        opened.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(obs_trace, "_annotation", annotation)
    stream, cfg = _workload(m=300, n=64, L=8)
    for kw in PALLAS.values():
        res = substream_match(stream, cfg, **kw)
    merge.merge_host(stream, res, cfg)
    match_epochs(stream, cfg, epochs=2, engine="mega")
    mwm_pipeline(stream, cfg, part1="pallas", K=8)
    assert opened == []
    substream_match(stream, cfg, schedule="mega", telemetry=obs.Telemetry())
    assert {"wave_schedule.assign", "copy.d2h", "layout.gather"} <= set(opened)


def test_traffic_bytes_pinned_on_a_small_mega_plan():
    """HBM bytes of one mega call: 16 B per streamed slot, and the bit
    block written once (and read once more when state is carried in);
    the VMEM rows a slot touches move none."""
    stream, cfg = _workload(m=600, n=128, L=8)
    tel = obs.Telemetry()
    substream_match(stream, cfg, schedule="mega", telemetry=tel)
    counters = tel.match_calls[-1].counters
    # 59 tiles of 2 x 8 slots in one grid program, a 4 KiB bit block
    assert counters["layout.num_tiles"] == 59
    assert counters["plan.tiles_per_block"] == 59
    assert counters["plan.bit_block_bytes"] == 4096
    assert counters["traffic.hbm_bytes"] == 59 * 16 * 16 + 4096 == 19_200
    assert traffic_bytes(944, 4096) == 19_200
    mb0 = np.zeros((cfg.n, 1), np.uint8)
    substream_match(stream, cfg, schedule="mega", telemetry=tel, mb0=mb0)
    assert tel.match_calls[-1].counters["traffic.hbm_bytes"] == 944 * 16 + 2 * 4096


def test_mwm_pipeline_records_its_merge():
    """The public entry hands its telemetry to the merge: ``merge.host``
    and its copies are recorded on the per-edge path too."""
    stream, cfg = _workload(m=300, n=64, L=8)
    tel = obs.Telemetry()
    idx, weight = mwm_pipeline(stream, cfg, part1="pallas", K=8, telemetry=tel)
    assert len(_spans(tel, "merge.host")) == 1
    assert tel.counters.get("merge.matched_edges") == len(idx)
    assert {e["args"]["what"] for e in _spans(tel, "copy.d2h")} == {
        "stream", "assigned", "weight",
    }
    assert tel.match_calls[-1].counters["kernel.trips"] == stream.num_edges
    idx2, weight2 = mwm_pipeline(stream, cfg, part1="pallas", K=8)
    np.testing.assert_array_equal(idx, idx2)
    assert weight == weight2


def test_match_epochs_records_state_spans():
    stream, cfg = _workload(m=300, n=64, L=8)
    tel = obs.Telemetry()
    match_epochs(stream, cfg, epochs=3, engine="mega", telemetry=tel)
    assert len(_spans(tel, "state.initial")) == 1
    folds = _spans(tel, "epoch.fold")
    assert [e["args"]["call"] for e in folds] == [0, 1, 2]


# ------------------------------------------------------- disabled path


def test_disabled_path_is_identity_objects():
    assert obs.DISABLED.span("a") is obs.NULL_SPAN
    assert obs.DISABLED.span("b", k=1) is obs.NULL_SPAN
    assert obs.DISABLED.counters is obs.NULL_COUNTERS
    assert obs.recorder(obs.DISABLED, "e", 10) is obs.NULL_RECORDER
    assert obs.recorder(None, "e", 10) is obs.NULL_RECORDER
    assert obs.DISABLED.match_calls == ()
    assert obs.DISABLED.events == ()
    with pytest.raises(RuntimeError):
        obs.DISABLED.write_chrome_trace("/tmp/nope.json")


def test_disabled_hot_loop_does_not_accumulate_allocations():
    """The no-op path may allocate transient call frames but must not
    retain anything per iteration (no event lists, no span objects)."""
    tel = obs.DISABLED
    rec = obs.recorder(tel, "hot", 1)
    # warm up any lazy interning before measuring
    with tel.span("hot"):
        pass
    tracemalloc.start()
    for _ in range(5000):
        with tel.span("hot"):
            pass
        tel.count("hot.counter")
        with rec.stage("layout"):
            pass
        with rec.span("copy.d2h", what="stream"):
            pass
        rec.put("gauge", 1)
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # 5000 iterations retaining even one small object each would hold
    # hundreds of KiB; the no-op path must stay near-zero
    assert current < 16_384, f"disabled path retained {current} bytes"


def test_disabled_engine_results_identical():
    stream, cfg = _workload()
    tel = obs.Telemetry()
    for kw in PALLAS.values():
        a = substream_match(stream, cfg, telemetry=tel, **kw)
        b = substream_match(stream, cfg, **kw)
        np.testing.assert_array_equal(np.asarray(a.assigned), np.asarray(b.assigned))


# -------------------------------------------------- record consistency


def test_consistency_problems_unit():
    good = {"schedule": 0.1, "pack": 0.0, "layout": 0.1, "compile": 0.0,
            "execute": 0.2}
    assert obs.consistency_problems(good, 0.5) == []
    probs = obs.consistency_problems({"schedule": 0.1}, 0.5)
    assert any("missing" in p for p in probs)
    probs = obs.consistency_problems({**good, "execute": -1.0}, 0.5)
    assert any("negative" in p for p in probs)
    probs = obs.consistency_problems(good, 0.1)
    assert any("exceeds wall" in p for p in probs)


@pytest.mark.parametrize("eng", list(PALLAS))
def test_stage_seconds_within_wall(eng):
    stream, cfg = _workload(m=500, n=96, L=8, eps=0.12, seed=eng.__hash__() % 7)
    tel = obs.Telemetry()
    substream_match(stream, cfg, telemetry=tel, **PALLAS[eng])
    rec = tel.match_calls[-1]
    assert rec.engine == f"pallas_{PALLAS[eng]['schedule']}"
    assert obs.consistency_problems(rec.stage_seconds, rec.wall_seconds) == []
    assert set(rec.stage_seconds) == set(obs.STAGES)


def test_compile_then_execute_labeling():
    """First dispatch of a jit variant lands in `compile`, repeats in
    `execute` — tracked process-wide, including disabled warmups."""
    stream, cfg = _workload(m=333, n=64, L=8, eps=0.17, seed=5)
    tel = obs.Telemetry()
    seg1 = PALLAS["mega_seg1"]
    substream_match(stream, cfg, telemetry=tel, **seg1)
    cold = tel.match_calls[-1]
    substream_match(stream, cfg, telemetry=tel, **seg1)
    warm = tel.match_calls[-1]
    assert cold.stage_seconds["compile"] > 0 and cold.stage_seconds["execute"] == 0
    assert warm.stage_seconds["compile"] == 0 and warm.stage_seconds["execute"] > 0
    assert cold.counters["jit.variant_miss"] == 1
    assert warm.counters["jit.variant_hit"] == 1
    # a warmup made with telemetry DISABLED still marks the variant warm
    stream2, cfg2 = _workload(m=334, n=64, L=8, eps=0.17, seed=6)
    substream_match(stream2, cfg2, **seg1)
    tel2 = obs.Telemetry()
    substream_match(stream2, cfg2, telemetry=tel2, **seg1)
    assert tel2.match_calls[-1].stage_seconds["compile"] == 0


def test_wave_counters_bit_exact_against_plan():
    stream, cfg = _workload(m=700, n=160, L=8)
    src, dst = np.asarray(stream.src), np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    tel = obs.Telemetry()
    substream_match(stream, cfg, telemetry=tel, **PALLAS["mega_seg1"])
    rec = tel.match_calls[-1]
    sch = wave_schedule(src, dst, valid=valid)
    plan = mega_plan(cfg.n, cfg.L, block_aligned_layout(sch, 1))
    assert rec.counters["plan.gather_bytes"] == plan.gather_bytes
    assert rec.counters["plan.bit_block_bytes"] == plan.nbytes
    assert rec.counters["plan.seg"] == plan.seg
    assert rec.counters["plan.block_s"] == plan.block_s
    for k, v in schedule_counters(sch).items():
        assert rec.counters[k] == v, k
    total = _round_up(max(sch.num_segments, 1), plan.block_s) * plan.seg
    assert rec.counters["traffic.hbm_bytes"] == traffic_bytes(total, plan.nbytes)


def test_mega_counters_bit_exact_against_plan():
    stream, cfg = _workload(m=700, n=160, L=8)
    src, dst = np.asarray(stream.src), np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    tel = obs.Telemetry()
    substream_match(stream, cfg, schedule="mega", telemetry=tel)
    rec = tel.match_calls[-1]
    sch = wave_schedule(src, dst, valid=valid)
    layout = block_aligned_layout(sch, MEGA_SEG_BLOCK)
    plan = mega_plan(cfg.n, cfg.L, layout)
    assert rec.counters["plan.gather_bytes"] == plan.gather_bytes
    assert rec.counters["plan.tile_bytes"] == plan.tile_bytes
    assert rec.counters["plan.tiles_per_block"] == plan.tiles_per_block
    assert rec.counters["layout.num_tiles"] == layout.num_tiles
    assert rec.counters["layout.padding_rows"] == (
        layout.num_segments - sch.num_segments
    )
    bslots = plan.seg_block * plan.seg
    total = _round_up(max(layout.num_tiles, 1), plan.tiles_per_block) * bslots
    assert rec.counters["traffic.hbm_bytes"] == traffic_bytes(total, plan.nbytes)


def test_counters_deterministic_across_runs():
    """Re-running the same call yields identical counters, except the
    jit hit/miss labels (cold vs warm is real state, not noise)."""
    stream, cfg = _workload(m=450, n=96, L=8)

    def counters_of(eng):
        tel = obs.Telemetry()
        substream_match(stream, cfg, telemetry=tel, **PALLAS[eng])
        return {
            k: v
            for k, v in tel.match_calls[-1].counters.items()
            if not k.startswith("jit.")
        }

    for eng in PALLAS:
        first = counters_of(eng)
        second = counters_of(eng)
        assert first == second
        assert first  # non-empty


def test_backend_event_per_call():
    """`resolve_interpret`'s auto flip is no longer silent: every
    substream_match call emits one structured backend event."""
    stream, cfg = _workload(m=200, n=64, L=8)
    tel = obs.Telemetry()
    substream_match(stream, cfg, schedule="edges", telemetry=tel)
    substream_match(stream, cfg, schedule="mega", telemetry=tel)
    evs = [e for e in tel.events if e["name"] == "substream_match.backend"]
    assert len(evs) == 2
    assert [e["engine"] for e in evs] == ["edges", "mega"]
    for e in evs:
        assert e["backend"] == jax.default_backend()
        assert isinstance(e["interpret"], bool)
        # on anything but a real TPU the auto policy interprets
        if e["backend"] != "tpu":
            assert e["interpret"] is True


def test_schedule_seconds_one_timing_path():
    """The deprecated WaveSchedule fields and the telemetry spans are
    views of the same stopwatch measurement — not two timers."""
    stream, _ = _workload(m=800, n=128, L=8)
    tel = obs.Telemetry()
    sch = wave_schedule(
        np.asarray(stream.src),
        np.asarray(stream.dst),
        valid=np.asarray(stream.valid),
        telemetry=tel,
    )
    evs = tel.chrome_trace()["traceEvents"]
    assign = next(e for e in evs if e["name"] == "wave_schedule.assign")
    pack = next(e for e in evs if e["name"] == "wave_schedule.pack")
    assert assign["dur"] == pytest.approx(sch.schedule_seconds * 1e6, rel=1e-9)
    assert pack["dur"] == pytest.approx(sch.pack_seconds * 1e6, rel=1e-9)
    # and the schedule geometry landed in the session counters
    assert tel.counters.get("schedule.num_waves") == sch.num_waves
    assert tel.counters.get("schedule.fill") == sch.fill


def test_schedule_casts_lie_outside_the_schedule_and_pack_stages():
    """``wave_schedule`` records ``prepare`` -> ``assign`` -> ``pack`` ->
    ``emit``, one after the other, and an engine call's ``schedule`` /
    ``pack`` stage seconds are the ``assign`` / ``pack`` spans alone."""
    stream, cfg = _workload(m=700, n=160, L=8)
    tel = obs.Telemetry()
    substream_match(stream, cfg, schedule="mega", telemetry=tel)
    names = ("prepare", "assign", "pack", "emit")
    (prepare, assign, pack, emit) = (
        _spans(tel, f"wave_schedule.{n}")[0] for n in names
    )
    assert all(len(_spans(tel, f"wave_schedule.{n}")) == 1 for n in names)
    steps = [prepare, assign, pack, emit]
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert {e["args"]["parent"] for e in steps} == {None}
    rec = tel.match_calls[-1]
    assert rec.stage_seconds["schedule"] * 1e6 == pytest.approx(assign["dur"], rel=1e-9)
    assert rec.stage_seconds["pack"] * 1e6 == pytest.approx(pack["dur"], rel=1e-9)


def test_merge_order_and_greedy_nest_inside_merge_host():
    stream, cfg = _workload(m=600, n=128, L=8)
    res = substream_match(stream, cfg, schedule="mega")
    tel = obs.Telemetry()
    merged = merge.merge_host(stream, res, cfg, telemetry=tel)
    np.testing.assert_array_equal(merged, merge.merge_host(stream, res, cfg))
    (host,) = _spans(tel, "merge.host")
    (order,) = _spans(tel, "merge.order")
    (greedy,) = _spans(tel, "merge.greedy")
    assert order["args"]["parent"] == greedy["args"]["parent"] == "merge.host"
    assert host["ts"] <= order["ts"]
    assert order["ts"] + order["dur"] <= greedy["ts"]
    assert greedy["ts"] + greedy["dur"] <= host["ts"] + host["dur"]
    assigned = np.asarray(res.assigned)
    groups = np.unique(assigned[assigned >= 0]).size
    assert groups > 1
    assert tel.counters.get("merge.greedy_groups") == groups
    assert tel.counters.get("merge.loop_edges") == 0


def test_merge_of_nothing_recorded_records_no_greedy_pass():
    stream, cfg = _workload(m=50, n=32, L=8)
    res = substream_match(stream, cfg, schedule="mega")
    res = type(res)(
        assigned=np.full(50, -1, np.int32), mb_packed=res.mb_packed, L=res.L
    )
    tel = obs.Telemetry()
    assert merge.merge_host(stream, res, cfg, telemetry=tel).size == 0
    assert len(_spans(tel, "merge.order")) == 1
    assert _spans(tel, "merge.greedy") == []


def test_xla_engines_and_merge_record():
    stream, cfg = _workload(m=400, n=96, L=8)
    tel = obs.Telemetry()
    res = mwm_waves(stream, cfg, telemetry=tel)
    assert tel.match_calls[-1].engine == "waves_xla"
    rounds.mwm_rounds(stream, cfg, telemetry=tel)
    assert tel.match_calls[-1].engine == "rounds"
    for rec in tel.match_calls:
        assert obs.consistency_problems(rec.stage_seconds, rec.wall_seconds) == []
    t = merge.merge_host(stream, res, cfg, telemetry=tel)
    assert tel.counters.get("merge.recorded_edges") == int(
        (np.asarray(res.assigned) >= 0).sum()
    )
    assert tel.counters.get("merge.matched_edges") == len(t)
    names = {e["name"] for e in tel.chrome_trace()["traceEvents"]}
    assert "merge.host" in names
    merge.merge_device(stream, res, cfg, telemetry=tel)
    assert "merge.device" in {e["name"] for e in tel.chrome_trace()["traceEvents"]}


def test_match_telemetry_asdict_json_ready():
    stream, cfg = _workload(m=300, n=64, L=8)
    tel = obs.Telemetry()
    substream_match(stream, cfg, telemetry=tel, **PALLAS["mega_seg1"])
    d = tel.match_calls[-1].asdict()
    json.dumps(d)  # must serialize
    assert list(d["stage_seconds"]) == list(obs.STAGES)
    assert d["edges_per_sec"] > 0
    assert d["engine"] == "pallas_mega"

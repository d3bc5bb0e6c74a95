"""Unit tests for the bench acceptance gate (`bench_throughput.check_report`).

The gate is a pure function (synthetic report dict in, verdict +
messages out) precisely so raising it — e.g. to ISSUE 6's
``mega >= waves_xla`` — cannot be silently broken by a bench refactor:
these tests pin the pass/fail semantics, the per-gate messages, and the
loud failure on structurally broken reports. Since the telemetry gates
landed, every engine row must also carry a complete ``stage_seconds``
split and a counter set matching the embedded ``expected_counters``
plan accounting bit-exactly — missing or inconsistent telemetry fails
the gate too. The guard gate additionally requires a clean per-graph
``validation`` record and ``fallback.count == 0`` on every Pallas row:
a bench number must come from the engine it is labeled with. Gate 7
(the recovery gate) requires every graph to embed a ``recovery`` block
from the resumable path with snapshot producer stall within budget, a
bit-exact killed-and-resumed result, and zero clean-path retries.
"""
import json
import sys

import pytest

from benchmarks.bench_throughput import (
    TARGET_FILL,
    TARGET_MEGA_VS_XLA,
    TARGET_SNAPSHOT_OVERHEAD_PCT,
    TARGET_SPEEDUP,
    check_report,
)

#: Gate messages: 3 perf gates + telemetry structure + plan counters
#: + the clean-path guard (validation clean, no fallback degradation)
#: + the recovery gate (snapshot stall, bit-exact resume, zero retries).
N_GATES = 7

_MEGA_EXPECT = {
    "plan.gather_bytes": 4352,
    "plan.bit_block_bytes": 8192,
    "traffic.hbm_bytes": 120_000,
}


def _engine_row(counters=None):
    return {
        "seconds_per_call": 0.01,
        "edges_per_sec": 1e6,
        "reps": 3,
        "backend": "cpu",
        "interpret": True,
        "stage_seconds": {
            "schedule": 0.001,
            "pack": 0.0005,
            "layout": 0.002,
            "compile": 0.1,
            "execute": 0.01,
        },
        "telemetry_wall_seconds": 0.2,
        "counters": dict(counters or {"stream.num_edges": 8192}),
    }


def _graph(scale=10, speedup=9.0, fill=0.7, mega=1.3):
    engines = {name: _engine_row() for name in ("scan", "waves_xla", "rounds")}
    engines["pallas_edges"] = _engine_row(
        {"stream.num_edges": 8192, "fallback.count": 0}
    )
    engines["pallas_mega"] = _engine_row(
        {"stream.num_edges": 8192, "fallback.count": 0, **_MEGA_EXPECT}
    )
    return {
        "scale": scale,
        "speedup_pallas_mega_vs_edges": speedup,
        "wave_fill": fill,
        "speedup_mega_vs_xla": mega,
        "validation": {
            "policy": "strict",
            "guard.num_edges": 8192,
            "guard.num_valid_in": 8192,
            "guard.dropped_edges": 0,
            "guard.num_problems": 0,
        },
        "expected_counters": {
            "pallas_mega": dict(_MEGA_EXPECT),
        },
        "recovery": {
            "epochs": 4,
            "engine": "mega",
            "chunked_seconds": 0.013,
            "chunked_snapshot_seconds": 0.019,
            "snapshot_stall_seconds": 0.0004,
            "snapshot_overhead_pct": 3.1,
            "flush_seconds": 0.003,
            "kill_after_epoch": 1,
            "recover_seconds": 0.018,
            "resumed_bit_exact": True,
            "clean_retries": 0,
        },
        "engines": engines,
    }


def _report(graphs):
    return {"benchmark": "bench_throughput", "graphs": graphs}


def test_all_gates_pass():
    ok, msgs = check_report(_report([_graph(10), _graph(12), _graph(14)]))
    assert ok
    assert len(msgs) == N_GATES
    assert all(m.startswith("PASS") for m in msgs)


def test_mega_gate_fails_below_xla():
    """The raised gate: mega slower than the XLA oracle on ANY scale fails."""
    graphs = [_graph(10), _graph(12, mega=0.97), _graph(14)]
    ok, msgs = check_report(_report(graphs))
    assert not ok
    fail = [m for m in msgs if m.startswith("FAIL")]
    assert len(fail) == 1
    assert "mega" in fail[0] and "scale 12" in fail[0]


def test_mega_gate_boundary_is_inclusive():
    ok, _ = check_report(_report([_graph(mega=TARGET_MEGA_VS_XLA)]))
    assert ok
    ok, _ = check_report(_report([_graph(mega=TARGET_MEGA_VS_XLA - 1e-6)]))
    assert not ok


def test_speedup_and_fill_gates_still_enforced():
    ok, msgs = check_report(_report([_graph(speedup=TARGET_SPEEDUP - 0.1)]))
    assert not ok and any("pallas_edges" in m for m in msgs if "FAIL" in m)
    ok, msgs = check_report(_report([_graph(fill=TARGET_FILL / 2)]))
    assert not ok and any("fill" in m for m in msgs if "FAIL" in m)


def test_worst_scale_is_named():
    """The message names the scale where the minimum occurred."""
    graphs = [_graph(10, fill=0.9), _graph(14, fill=0.51)]
    ok, msgs = check_report(_report(graphs))
    assert ok
    fill_msg = next(m for m in msgs if "fill" in m)
    assert "scale 14" in fill_msg


def test_broken_report_fails_loudly():
    """No graphs / missing keys can never pass vacuously."""
    ok, msgs = check_report({})
    assert not ok and "no graphs" in msgs[0]
    ok, msgs = check_report(_report([]))
    assert not ok
    g = _graph()
    del g["speedup_mega_vs_xla"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("missing" in m for m in msgs)


def test_missing_stage_seconds_fails():
    """An engine row without its telemetry stage split fails loudly."""
    g = _graph()
    del g["engines"]["pallas_mega"]["stage_seconds"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    msg = next(m for m in msgs if "stage_seconds" in m and m.startswith("FAIL"))
    assert "pallas_mega" in msg


def test_missing_stage_key_fails():
    """All five canonical stage keys are required on every row."""
    g = _graph()
    del g["engines"]["rounds"]["stage_seconds"]["compile"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("compile" in m and "rounds" in m for m in msgs)


def test_inconsistent_stage_sum_fails():
    """Stage sums exceeding the instrumented wall time fail the gate
    (stages are disjoint subintervals, so the sum can never exceed it)."""
    g = _graph()
    g["engines"]["scan"]["stage_seconds"]["execute"] = 10.0
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("exceeds wall" in m for m in msgs)


def test_empty_counters_fails():
    g = _graph()
    g["engines"]["waves_xla"]["counters"] = {}
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("no counters" in m and "waves_xla" in m for m in msgs)


def test_plan_counter_mismatch_fails_bit_exactly():
    """A single off-by-one in the emitted gather bytes is a gate failure —
    the counters must equal the recomputed plan accounting exactly."""
    g = _graph()
    g["engines"]["pallas_mega"]["counters"]["plan.gather_bytes"] += 1
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any(
        "plan.gather_bytes" in m and "pallas_mega" in m for m in msgs
    )
    g2 = _graph()
    del g2["engines"]["pallas_mega"]["counters"]["traffic.hbm_bytes"]
    ok, msgs = check_report(_report([g2]))
    assert not ok
    assert any("traffic.hbm_bytes" in m and "missing" in m for m in msgs)


def test_missing_expected_counters_fails():
    """A report that stops embedding the plan accounting cannot pass."""
    g = _graph()
    del g["expected_counters"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("expected_counters" in m for m in msgs)


def test_missing_validation_block_fails():
    """A report that stops recording the guard validation cannot pass."""
    g = _graph()
    del g["validation"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("no validation block" in m for m in msgs)


def test_dirty_clean_path_fails():
    """Any dropped edge / detected problem on the bench path is a FAIL —
    the clean workload generator must never need sanitizing."""
    g = _graph()
    g["validation"]["guard.dropped_edges"] = 3
    g["validation"]["guard.num_problems"] = 1
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("guard.dropped_edges = 3" in m for m in msgs)
    assert any("guard.num_problems = 1" in m for m in msgs)


def test_nonzero_fallback_count_fails():
    """A Pallas row that silently degraded down the cascade fails the
    gate — its number is not the engine it is labeled with."""
    g = _graph()
    g["engines"]["pallas_mega"]["counters"]["fallback.count"] = 2
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any(
        "pallas_mega" in m and "fallback.count = 2" in m for m in msgs
    )


def test_missing_fallback_counter_fails():
    """Dropping the counter (e.g. running with on_plan_failure='raise')
    fails loudly rather than passing vacuously."""
    g = _graph()
    del g["engines"]["pallas_mega"]["counters"]["fallback.count"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any(
        "pallas_mega" in m and "no fallback.count" in m for m in msgs
    )


def test_non_pallas_rows_exempt_from_fallback_counter():
    """The XLA/rounds engines have no cascade; the guard gate only
    inspects pallas_* rows."""
    g = _graph()
    assert "fallback.count" not in g["engines"]["waves_xla"]["counters"]
    ok, _ = check_report(_report([g]))
    assert ok


def test_snapshot_overhead_gate_boundary_is_inclusive():
    """Gate 7: overhead exactly at the target passes, above fails with a
    message naming the scale and the measured percentage."""
    g = _graph()
    g["recovery"]["snapshot_overhead_pct"] = TARGET_SNAPSHOT_OVERHEAD_PCT
    ok, _ = check_report(_report([g]))
    assert ok
    g["recovery"]["snapshot_overhead_pct"] = TARGET_SNAPSHOT_OVERHEAD_PCT + 0.01
    ok, msgs = check_report(_report([g]))
    assert not ok
    msg = next(m for m in msgs if "recovery" in m and m.startswith("FAIL"))
    assert "snapshot overhead" in msg and "scale 10" in msg


def test_non_bit_exact_resume_fails():
    """Gate 7: a resumed result that diverged from the one-shot run is a
    correctness failure, whatever the overhead says."""
    g = _graph()
    g["recovery"]["resumed_bit_exact"] = False
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("not bit-exact" in m for m in msgs)


def test_clean_path_retries_fail():
    """Gate 7: the guard firing on an uninjected run means the engines
    are flaky (or the guard misclassifies) — never acceptable."""
    g = _graph()
    g["recovery"]["clean_retries"] = 2
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("clean_retries = 2" in m for m in msgs)


def test_missing_recovery_block_fails():
    """Gate 7 fails loudly when the recovery block (or its gated
    overhead field) is missing — a bench refactor that stops measuring
    the resumable path cannot pass vacuously."""
    g = _graph()
    del g["recovery"]
    ok, msgs = check_report(_report([g]))
    assert not ok
    assert any("no recovery block" in m for m in msgs)
    g2 = _graph()
    del g2["recovery"]["snapshot_overhead_pct"]
    ok, msgs = check_report(_report([g2]))
    assert not ok
    assert any("no snapshot_overhead_pct" in m for m in msgs)


def test_recovery_gate_enforced_on_every_graph():
    """A single over-budget graph fails even when the others pass."""
    g10, g12 = _graph(10), _graph(12)
    g12["recovery"]["snapshot_overhead_pct"] = 40.0
    ok, msgs = check_report(_report([g10, g12]))
    assert not ok
    msg = next(m for m in msgs if "recovery" in m and m.startswith("FAIL"))
    assert "scale 12" in msg and "scale 10" not in msg


def test_check_exits_nonzero_with_message(monkeypatch, capsys):
    """CLI wiring: `--check` on a failing report exits non-zero via
    SystemExit with a message, after printing each gate verdict — no
    bare assert anywhere on the path. The bench itself is stubbed out
    (run_report monkeypatched) so this stays a unit test."""
    import benchmarks.bench_throughput as bt

    bad = _report([_graph(10, mega=0.5)])
    monkeypatch.setattr(
        bt, "run_report", lambda **kw: ([("row", 1.0, "derived")], bad)
    )
    monkeypatch.setattr(
        sys, "argv", ["bench_throughput", "--check", "--no-json"]
    )
    with pytest.raises(SystemExit) as exc:
        bt.main()
    assert exc.value.code not in (0, None)
    assert "bench gate FAILED" in str(exc.value.code)
    out = capsys.readouterr().out
    assert "# gate:" in out and "FAIL" in out

    good = _report([_graph(10)])
    monkeypatch.setattr(
        bt, "run_report", lambda **kw: ([("row", 1.0, "derived")], good)
    )
    bt.main()  # all gates pass: returns normally, prints PASS lines
    out = capsys.readouterr().out
    assert "PASS" in out and "FAIL" not in out


def test_trace_flag_writes_chrome_trace(monkeypatch, capsys, tmp_path):
    """CLI wiring: `--trace out.json` dumps the session's Chrome trace."""
    import benchmarks.bench_throughput as bt

    good = _report([_graph(10)])
    monkeypatch.setattr(
        bt, "run_report", lambda **kw: ([("row", 1.0, "derived")], good)
    )
    out_path = tmp_path / "trace.json"
    monkeypatch.setattr(
        sys, "argv", ["bench_throughput", "--no-json", "--trace", str(out_path)]
    )
    bt.main()
    trace = json.loads(out_path.read_text())
    assert "traceEvents" in trace and isinstance(trace["traceEvents"], list)


"""The chip benchmark's harness, driven on the CPU at tiny sizes.

Every cell of ``BENCHMARK.json``, and every traffic mix of
``perfbench/traffic`` on ``kron16``, runs through ``harness.run_cell``
with its own traffic and entry but a tiny graph of its configuration's
family (Pallas in interpret mode), and must come out correct against
the plain reference. The control (the reference in bfloat16) and each
fault a cell can have, planted under the timed path, must come out not
correct.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from perfbench import control, harness, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = harness.load_spec(ROOT)
#: (configuration, traffic) pairs: the benchmark's cells, then every
#: other mix on kron16 (mixes kept for cells a later change adds)
PAIRS = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
PAIRS += [
    ("kron16", p.stem) for p in sorted((ROOT / "perfbench" / "traffic").glob("*.json"))
    if ("kron16", p.stem) not in PAIRS
]
CELLS = [f"{c}.{t}" for c, t in PAIRS]
#: Each family at a size interpret mode runs in well under a second.
TINY = {
    "kronecker": {"family": "kronecker", "scale": 7, "n": 128, "edge_factor": 4,
                  "initiator": [0.57, 0.19, 0.19, 0.05], "seed": 0},
    "delaunay": {"family": "delaunay", "n": 256, "seed": 0},
}
SEED = 2**31 + 12345  # wider than a signed 32-bit integer


def tiny_cell(name):
    config_name, traffic_name = name.split(".")
    spec = dict(SPEC, workloads=[{
        "name": name, "config": config_name, "traffic": traffic_name, "chips": 1,
    }])
    _, config, traffic, e2e, layer = harness.cell_parts(spec, name, ROOT)
    config = dict(config, graph=TINY[config["graph"]["family"]])
    if "epochs" in traffic:
        traffic = dict(traffic, epochs=4)
    return config, traffic, e2e, layer


def run_tiny(name, entry=None, seed=SEED, seconds=0.2):
    config, traffic, e2e, layer = tiny_cell(name)
    return harness.run_cell(
        config, traffic, e2e, layer, seed, seconds, False, time.perf_counter(),
        cache=None, entry=entry,
    )


@pytest.mark.parametrize("name", CELLS)
def test_cell_matches_reference(name):
    result, lines = run_tiny(name)
    assert result["correct"], (result, lines)
    assert result["attempted"] >= 1 and result["failed"] == 0
    names = {m["name"] for m in SPEC["end_to_end"] if name in m.get("workloads", [name])}
    assert {"edges_per_s", "setup_s"} <= names == set(result["metrics"])
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "check"
    assert all(v["value"] <= v["limit"] for v in result["check"].values())
    assert lines[-1].startswith("check ")


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    _, traffic, _, _ = tiny_cell(name)
    program = __import__(f"perfbench.entries.{traffic['entry']}", fromlist=["job"])
    # seed 1: at this tiny size the bfloat16 rounding changes the merged
    # matching too, which is all the edges entry returns
    result, _ = run_tiny(name, entry=control.control_entry(program), seed=1, seconds=0.01)
    assert not result["correct"]
    assert any(v["value"] > v["limit"] for v in result["check"].values())


def _faulty(program, fault):
    """The program's entry with its answer broken where it is produced."""

    def job(wl, params, ctx):
        if fault == "half_left_out":
            weight = wl.weight.copy()
            weight[wl.m // 2:] = 0.0  # admitted by no substream: never processed
            return program.job(dataclasses.replace(wl, weight=weight), params, ctx)
        ans = program.job(wl, params, ctx)
        ans = harness._host_answer(ans)
        if fault == "answer_altered":
            if ans.assigned is not None:
                ans.assigned = ans.assigned.copy()
                ans.assigned[np.argmax(ans.assigned >= 0)] = -1
            ans.merged = ans.merged[1:]
        elif fault == "state_unchanged":
            ans.state = np.zeros_like(ans.state)
        return ans

    return types.SimpleNamespace(ORDER=program.ORDER, job=job)


FAULTS = [
    (name, fault)
    for name in CELLS
    for fault in ("answer_altered", "half_left_out", "state_unchanged")
    if not (fault == "state_unchanged" and name.endswith(".edges"))  # Part 2 only
]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_is_not_correct(name, fault):
    _, traffic, _, _ = tiny_cell(name)
    program = __import__(f"perfbench.entries.{traffic['entry']}", fromlist=["job"])
    result, _ = run_tiny(name, entry=_faulty(program, fault), seconds=0.01)
    assert not result["correct"]
    assert result["failed"] >= 1


def test_epoch_state_not_carried_is_not_correct(monkeypatch):
    """The epoch path with each epoch's state handed on unchanged."""
    from repro.core import state as state_mod

    advance = state_mod.MatchState.advance

    def stale(self, result, end):
        return dataclasses.replace(advance(self, result, end), mb=self.mb)

    monkeypatch.setattr(state_mod.MatchState, "advance", stale)
    result, _ = run_tiny("kron16.epochs64", seconds=0.01)
    assert not result["correct"]


def test_workload_is_fixed_by_the_seed():
    config, _, _, _ = tiny_cell("kron16.mega")
    a = harness.make_workload(config, SEED, cache=None)
    b = harness.make_workload(config, SEED, cache=None)
    c = harness.make_workload(config, SEED + 1, cache=None)
    for x in ("src", "dst", "weight"):
        np.testing.assert_array_equal(getattr(a, x), getattr(b, x))
    assert a.m == c.m and not np.array_equal(a.src, c.src)
    # another seed relabels and reorders the same graph: same degrees
    deg = lambda w: np.sort(np.bincount(np.r_[w.src, w.dst], minlength=w.n))  # noqa: E731
    np.testing.assert_array_equal(deg(a), deg(c))


def test_graph_cache_round_trip(tmp_path):
    config, _, _, _ = tiny_cell("delaunay20.mega")
    first = harness.graph(config, tmp_path)
    assert len(list((tmp_path / "graphs").glob("*.npz"))) == 1
    again = harness.graph(config, tmp_path)
    for x, y in zip(first, again):
        np.testing.assert_array_equal(x, y)


def test_reference_matches_a_hand_example():
    # path 0-1-2-3 with one self-loop; thresholds 1, 1.1, 1.21 (L = 3)
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 3], np.int32)
    w = np.array([1.05, 1.3, 1.15, 9.0], np.float32)
    ans = reference.solve(src, dst, w, n=4, L=3, eps=0.1)
    # edge 0 takes substream 0; edge 1 takes 1 and 2 (vertex 1 is taken
    # in 0); edge 2 finds vertex 2 taken in 1, so takes 0 only
    np.testing.assert_array_equal(ans.assigned, [0, 2, 0, -1])
    np.testing.assert_array_equal(ans.state[:, 0], [0b001, 0b111, 0b111, 0b001])
    np.testing.assert_array_equal(ans.merged, [1])
    assert ans.weight == pytest.approx(1.3)


def _run_main(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "ALLOW_MULTIPLE_LIBTPU_LOAD"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "kron16.mega",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_without_a_tpu_fails_with_no_result():
    out = _run_main(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_without_the_program_fails_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_main(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""

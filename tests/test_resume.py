"""Resumable chunked streaming: epoch parity, crash matrix, snapshots.

The standing robustness gate for the epoch executor
(:func:`repro.kernels.substream_match.ops.match_epochs`):

* **epoch parity** — every engine chunked into E ∈ {1, 2, 7} epochs is
  bit-identical to the one-shot scan oracle, packed and dense;
* **crash matrix** — kill at every epoch boundary × all six engines ×
  both storage layouts, resume from the latest snapshot, assert
  bit-identity plus a clean ``check_matching`` postcondition;
* **snapshot protocol** — torn commits are invisible (fsync'd
  write-tmp-rename), fingerprint mismatches and corrupt payloads fail
  with structured errors, async saves land.

The graph is small (m = 98 = 7 x 14, so E = 7 slices are equal-length
and the jit variants are shared across kill points) but adversarial
enough: duplicate edges, self-loops, an invalid-masked tail, L % 8 != 0.
"""
import numpy as np
import pytest

from repro import obs
from repro.checkpoint import (
    SnapshotCorruptError,
    SnapshotManager,
    SnapshotMismatchError,
)
from repro.core import MatchState, check_matching
from repro.core.matching import mwm_scan
from repro.core.state import fingerprint_for
from repro.core.types import EdgeStream, SubstreamConfig
from repro.kernels.substream_match.ops import (
    EPOCH_ENGINES,
    epoch_bounds,
    match_epochs,
)
from repro.testing import faultline

N, M, L = 44, 98, 12
EPOCHS = 7

#: Every epoch engine, plus the megakernel at one segment per tile (its
#: smallest slot working set, the fallback ladder's second rung).
ENGINES = {name: {"engine": name} for name in EPOCH_ENGINES}
ENGINES["mega_seg1"] = {"engine": "mega", "seg_block": 1}


def _build_stream():
    rng = np.random.default_rng(42)
    src = rng.integers(0, N, M).astype(np.int32)
    dst = rng.integers(0, N, M).astype(np.int32)
    w = rng.uniform(1.0, 60.0, M).astype(np.float32)
    src[10] = dst[10] = 7  # self-loop
    src[20], dst[20] = src[21], dst[21] = 3, 9  # duplicate edge
    stream = EdgeStream.from_numpy(src, dst, w)
    # mask a few edges invalid so the mask must survive epoch slicing
    valid = np.asarray(stream.valid).copy()
    valid[[5, 50, 95]] = False
    return EdgeStream(
        src=stream.src, dst=stream.dst, weight=stream.weight,
        valid=np.asarray(valid),
    )


STREAM = _build_stream()
CFG = SubstreamConfig(n=N, L=L)
ORACLE = mwm_scan(STREAM, CFG)
ORACLE_ASSIGNED = np.asarray(ORACLE.assigned)
ORACLE_MB = np.asarray(ORACLE.mb)


def _assert_bit_identical(result):
    assert np.array_equal(np.asarray(result.assigned), ORACLE_ASSIGNED)
    assert np.array_equal(np.asarray(result.mb), ORACLE_MB)


# ------------------------------------------------------------ epoch bounds


def test_epoch_bounds_properties():
    for m in (0, 1, 7, 98, 101):
        for e in (1, 2, 3, 7):
            b = epoch_bounds(m, e)
            assert b[0] == 0 and b[-1] == m and len(b) == e + 1
            assert all(x <= y for x, y in zip(b, b[1:]))
    with pytest.raises(ValueError):
        epoch_bounds(10, 0)


# ------------------------------------------------------------ epoch parity


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "dense"])
@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("epochs", [1, 2, 7])
def test_epoch_parity(engine, epochs, packed):
    """Chunked == one-shot, bit for bit, for every engine and E."""
    out = match_epochs(
        STREAM, CFG, epochs=epochs, packed=packed, interpret=True,
        **ENGINES[engine],
    )
    assert out.is_packed == packed
    _assert_bit_identical(out)


def test_epoch_index_telemetry():
    tel = obs.Telemetry()
    match_epochs(STREAM, CFG, epochs=4, engine="scan", telemetry=tel)
    events = [e for e in tel.events if e["name"] == "epoch.index"]
    assert [e["epoch"] for e in events] == [0, 1, 2, 3]
    assert events[0]["start"] == 0 and events[-1]["end"] == M
    assert tel.counters.asdict()["epoch.count"] == 4


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        match_epochs(STREAM, CFG, engine="fpga")


# ------------------------------------------------------------- crash matrix


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "dense"])
@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("kill", range(EPOCHS))
def test_kill_and_resume_bit_identical(tmp_path, engine, kill, packed):
    """Kill after epoch ``kill`` snapshots, resume from the latest
    snapshot, and the stitched run equals the one-shot oracle with a
    clean check_matching postcondition."""
    kw = dict(
        epochs=EPOCHS, packed=packed, interpret=True, **ENGINES[engine],
    )
    with pytest.raises(faultline.SimulatedCrash):
        match_epochs(
            STREAM, CFG, snapshots=SnapshotManager(tmp_path, async_save=False),
            epoch_hook=faultline.kill_at_epoch(kill), **kw,
        )
    tel = obs.Telemetry()
    out = match_epochs(
        STREAM, CFG, snapshots=SnapshotManager(tmp_path, async_save=False),
        telemetry=tel, **kw,
    )
    _assert_bit_identical(out)
    check_matching(out, STREAM, CFG)
    # the resume replayed only the remaining suffix
    replayed = [e["epoch"] for e in tel.events if e["name"] == "epoch.index"]
    assert replayed == list(range(kill + 1, EPOCHS))


def test_resume_replays_nothing_when_complete(tmp_path):
    snaps = SnapshotManager(tmp_path, async_save=False)
    out1 = match_epochs(
        STREAM, CFG, epochs=3, engine="scan", snapshots=snaps
    )
    tel = obs.Telemetry()
    out2 = match_epochs(
        STREAM, CFG, epochs=3, engine="scan", telemetry=tel,
        snapshots=SnapshotManager(tmp_path, async_save=False),
    )
    _assert_bit_identical(out1)
    _assert_bit_identical(out2)
    assert [e for e in tel.events if e["name"] == "epoch.index"] == []


def test_resume_works_across_engines(tmp_path):
    """Snapshots are engine-agnostic: a run killed under one engine can
    be resumed by another (the state is just (assigned, mb, pos))."""
    with pytest.raises(faultline.SimulatedCrash):
        match_epochs(
            STREAM, CFG, epochs=EPOCHS, engine="mega", interpret=True,
            snapshots=SnapshotManager(tmp_path, async_save=False),
            epoch_hook=faultline.kill_at_epoch(2),
        )
    out = match_epochs(
        STREAM, CFG, epochs=EPOCHS, engine="scan",
        snapshots=SnapshotManager(tmp_path, async_save=False),
    )
    _assert_bit_identical(out)


def test_async_snapshots_land(tmp_path):
    snaps = SnapshotManager(tmp_path, keep=0, async_save=True)
    out = match_epochs(
        STREAM, CFG, epochs=4, engine="scan", snapshots=snaps
    )
    _assert_bit_identical(out)
    assert snaps.all_positions() == epoch_bounds(M, 4)[1:]


def test_snapshot_telemetry_counters(tmp_path):
    tel = obs.Telemetry()
    snaps = SnapshotManager(tmp_path, async_save=False, telemetry=tel)
    match_epochs(STREAM, CFG, epochs=3, engine="scan", snapshots=snaps,
                 telemetry=tel)
    counters = tel.counters.asdict()
    assert counters["snapshot.count"] == 3
    spans = [
        e for e in tel.tracer.events
        if e["name"] == "snapshot.save" and e["ph"] == "X"
    ]
    assert len(spans) == 3


# ------------------------------------------------------- snapshot validation


def test_fingerprint_mismatch_rejected(tmp_path):
    snaps = SnapshotManager(tmp_path, async_save=False)
    with pytest.raises(faultline.SimulatedCrash):
        match_epochs(
            STREAM, CFG, epochs=4, engine="scan", snapshots=snaps,
            epoch_hook=faultline.kill_at_epoch(1),
        )
    other = EdgeStream(
        src=STREAM.src, dst=STREAM.dst, weight=STREAM.weight + 1.0,
        valid=STREAM.valid,
    )
    with pytest.raises(SnapshotMismatchError):
        match_epochs(
            other, CFG, epochs=4, engine="scan",
            snapshots=SnapshotManager(tmp_path, async_save=False),
        )


def test_storage_layout_mismatch_rejected(tmp_path):
    """packed and dense runs fingerprint differently — resuming a packed
    snapshot into a dense run is a mismatch, not a crash."""
    snaps = SnapshotManager(tmp_path, async_save=False)
    with pytest.raises(faultline.SimulatedCrash):
        match_epochs(
            STREAM, CFG, epochs=4, engine="scan", packed=True,
            snapshots=snaps, epoch_hook=faultline.kill_at_epoch(1),
        )
    with pytest.raises(SnapshotMismatchError):
        match_epochs(
            STREAM, CFG, epochs=4, engine="scan", packed=False,
            snapshots=SnapshotManager(tmp_path, async_save=False),
        )


def test_explicit_state_fingerprint_checked():
    other = EdgeStream(
        src=STREAM.src, dst=STREAM.dst, weight=STREAM.weight + 1.0,
        valid=STREAM.valid,
    )
    stale = MatchState.initial(other, CFG, True)
    with pytest.raises(SnapshotMismatchError):
        match_epochs(STREAM, CFG, epochs=2, engine="scan", state=stale)


def test_corrupt_snapshot_rejected(tmp_path):
    """A torn payload (cursors from one epoch, assigned from another)
    fails the structural integrity check at restore."""
    snaps = SnapshotManager(tmp_path, async_save=False)
    with pytest.raises(faultline.SimulatedCrash):
        match_epochs(
            STREAM, CFG, epochs=4, engine="scan", snapshots=snaps,
            epoch_hook=faultline.kill_at_epoch(2),
        )
    # tamper: rewrite the recorded-count cursors inside the npz payload
    import glob
    import os

    latest = sorted(glob.glob(os.path.join(tmp_path, "step_*")))[-1]
    path = os.path.join(latest, "match_state.npz")
    with np.load(path) as data:
        arrays = {k: data[k].copy() for k in data.files}
    arrays["recorded_counts"] = arrays["recorded_counts"] + 1
    np.savez(path, **arrays)
    with pytest.raises(SnapshotCorruptError):
        match_epochs(
            STREAM, CFG, epochs=4, engine="scan",
            snapshots=SnapshotManager(tmp_path, async_save=False),
        )


def test_torn_commit_invisible(tmp_path):
    """kill-mid-snapshot (power loss before the durable rename): the
    partial commit is never visible as a step and the previous snapshot
    remains the latest; a restarted manager recovers cleanly."""
    snaps = SnapshotManager(tmp_path, async_save=False)
    match_epochs(STREAM, CFG, epochs=2, engine="scan", snapshots=snaps)
    committed = snaps.all_positions()
    assert committed == epoch_bounds(M, 2)[1:]

    broken = SnapshotManager(tmp_path, async_save=False)
    faultline.kill_mid_snapshot(broken)
    state = MatchState.initial(STREAM, CFG, True)
    with pytest.raises(faultline.SimulatedCrash):
        broken.save(state)
    # the torn tmp dir exists but is not a committed step
    fresh = SnapshotManager(tmp_path, async_save=False)
    assert fresh.all_positions() == committed
    out = match_epochs(
        STREAM, CFG, epochs=2, engine="scan", snapshots=fresh
    )
    _assert_bit_identical(out)


def test_empty_directory_is_fresh_start(tmp_path):
    out = match_epochs(
        STREAM, CFG, epochs=2, engine="scan",
        snapshots=SnapshotManager(tmp_path, async_save=False),
    )
    _assert_bit_identical(out)


# ------------------------------------------------------------- MatchState


def test_match_state_initial_clean():
    st = MatchState.initial(STREAM, CFG, True)
    assert st.pos == 0 and not st.done and st.mb0 is None
    assert st.problems() == []


def test_match_state_round_trip():
    st = MatchState.initial(STREAM, CFG, True)
    out = match_epochs(STREAM, CFG, epochs=1, engine="scan")
    st = st.advance(out, M)
    assert st.done
    rebuilt = MatchState.from_arrays(st.metadata(), st.to_arrays())
    assert rebuilt.problems() == []
    _assert_bit_identical(rebuilt.result())


def test_match_state_detects_torn_state():
    st = MatchState.initial(STREAM, CFG, True)
    out = match_epochs(STREAM, CFG, epochs=1, engine="scan")
    st = st.advance(out, M)
    torn = MatchState(
        fingerprint=st.fingerprint, pos=st.pos, num_edges=st.num_edges,
        n=st.n, L=st.L, packed=st.packed, assigned=st.assigned,
        mb=st.mb, recorded_counts=st.recorded_counts + 1,
    )
    assert any("recorded_counts" in p for p in torn.problems())


def test_match_state_rejects_partial_result():
    st = MatchState.initial(STREAM, CFG, True)
    with pytest.raises(ValueError):
        st.result()


def test_fingerprint_sensitivity():
    base = fingerprint_for(STREAM, CFG, True)
    assert fingerprint_for(STREAM, CFG, False) != base
    assert fingerprint_for(STREAM, SubstreamConfig(n=N, L=L + 1), True) != base
    other = EdgeStream(
        src=STREAM.src, dst=STREAM.dst, weight=STREAM.weight,
        valid=np.zeros(M, bool),
    )
    assert fingerprint_for(other, CFG, True) != base


# -------------------------------------------------- fallback inside epochs


def test_epochs_with_fallback_cascade(tmp_path):
    """A permanent device fault inside an epoch degrades through the
    PR 8 cascade (mega -> ... -> scan) and the chunked run still
    matches the oracle; snapshots keep committing."""
    snaps = SnapshotManager(tmp_path, keep=0, async_save=False)
    with faultline.failing("mega_device"):
        out = match_epochs(
            STREAM, CFG, epochs=3, engine="mega", interpret=True,
            on_plan_failure="fallback", snapshots=snaps,
        )
    _assert_bit_identical(out)
    assert snaps.all_positions() == epoch_bounds(M, 3)[1:]

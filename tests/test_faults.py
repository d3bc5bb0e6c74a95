"""Fault-injection suite for the guarded matching pipeline.

Uses the deterministic injector (:mod:`repro.testing.faultline`) to prove
the three guard-layer claims:

1. **strict mode catches every injected input fault** — each poisoned
   stream raises a :class:`StreamValidationError` naming the fault kind
   and the planted positions; sanitize drops exactly those edges and is
   bit-identical to a manual drop;
2. **the cascade lands on a correct engine for every injected
   plan/compile fault** — ``on_plan_failure="fallback"`` survives forced
   planner/device/oracle failures (and stale precomputed schedules) with
   a result bit-identical to the scan baseline, recording ``fallback``
   events + counters, and raises :class:`FallbackExhaustedError` naming
   every attempt when *nothing* is left;
3. **the invariant checker flags every injected result corruption** —
   out-of-range/padding/self-loop/ineligible/duplicate ``assigned``
   rewrites and bit-plane flips all raise
   :class:`MatchingInvariantError`.
"""
import re

import numpy as np
import pytest

from repro import obs
from repro.core import (
    EdgeStream,
    StreamValidationError,
    SubstreamConfig,
    check_matching,
    matching_problems,
    merge_host,
    mwm_scan,
    validate_stream,
)
from repro.core.guard import MatchingInvariantError
from repro.graph.waves import validate_schedule, wave_schedule
from repro.kernels.substream_match.ops import (
    FallbackExhaustedError,
    substream_match,
)
from repro.testing import faultline


def _stream(seed=0, n=32, m=120, L=12, pad=0):
    rng = np.random.default_rng(seed)
    stream = EdgeStream.from_numpy(
        rng.integers(0, n, m),
        rng.integers(0, n, m),
        rng.uniform(0.5, 4.0, m),
        n_pad=m + pad,
    )
    return stream, SubstreamConfig(n=n, L=L)


# ---------------------------------------------------------------------------
# 1. Input faults: strict catches, sanitize repairs
# ---------------------------------------------------------------------------

INPUT_FAULTS = {
    "id_past_n": lambda s, cfg: faultline.poison_ids(s, cfg.n, (3, 7), "past_n"),
    "id_sacrificial": lambda s, cfg: faultline.poison_ids(
        s, cfg.n, (0, 11), "sacrificial"
    ),
    "id_negative": lambda s, cfg: faultline.poison_ids(s, cfg.n, (5,), "negative"),
    "id_int_max": lambda s, cfg: faultline.poison_ids(s, cfg.n, (2, 9), "int_max"),
    "weight_nan": lambda s, cfg: faultline.poison_weights(s, (4, 8), "nan"),
    "weight_posinf": lambda s, cfg: faultline.poison_weights(s, (1,), "posinf"),
    "weight_neginf": lambda s, cfg: faultline.poison_weights(s, (6, 13), "neginf"),
    "weight_negative": lambda s, cfg: faultline.poison_weights(s, (10,), "negative"),
}


@pytest.mark.parametrize("fault", sorted(INPUT_FAULTS))
def test_strict_catches_every_input_fault(fault):
    stream, cfg = _stream()
    dirty, info = INPUT_FAULTS[fault](stream, cfg)
    with pytest.raises(StreamValidationError) as exc:
        validate_stream(dirty, cfg.n, policy="strict")
    err = exc.value
    kinds = {p.kind for p in err.problems}
    assert info.kind in kinds, f"{fault}: {kinds} misses {info.kind}"
    prob = next(p for p in err.problems if p.kind == info.kind)
    assert set(info.positions) <= set(prob.indices)
    assert prob.count == len(info.positions)
    # the message is service-log ready: kind + positions, no debugger needed
    assert info.kind in str(err)
    assert str(list(info.positions)[0]) in str(err)


@pytest.mark.parametrize("fault", sorted(INPUT_FAULTS))
def test_sanitize_drops_exactly_the_faulted_edges(fault):
    stream, cfg = _stream()
    dirty, info = INPUT_FAULTS[fault](stream, cfg)
    tel = obs.Telemetry()
    clean, report = validate_stream(dirty, cfg.n, policy="sanitize", telemetry=tel)
    assert report.num_dropped == len(info.positions)
    valid = np.asarray(clean.valid)
    assert not valid[list(info.positions)].any()
    # dropped edges aside, the stream is untouched
    keep = np.ones(stream.num_edges, bool)
    keep[list(info.positions)] = False
    assert (valid[keep] == np.asarray(dirty.valid)[keep]).all()
    # telemetry observed the repair
    assert tel.counters.get("guard.dropped_edges") == len(info.positions)
    assert any(e["name"] == "guard.sanitize" for e in tel.events)
    # and the repaired stream is bit-identical to a manual drop
    manual = EdgeStream(
        src=dirty.src, dst=dirty.dst, weight=dirty.weight,
        valid=np.asarray(dirty.valid) & keep,
    )
    want = mwm_scan(manual, cfg)
    got = mwm_scan(clean, cfg)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def test_off_policy_is_identity():
    stream, cfg = _stream()
    dirty, _ = faultline.poison_ids(stream, cfg.n, (3,), "negative")
    out, report = validate_stream(dirty, cfg.n, policy="off")
    assert out is dirty
    assert report.ok and report.num_dropped == 0


def test_validate_policy_threaded_through_substream_match():
    stream, cfg = _stream()
    dirty, info = faultline.poison_weights(stream, (4, 8), "nan")
    with pytest.raises(StreamValidationError):
        substream_match(dirty, cfg, interpret=True, validate="strict")
    want = mwm_scan(stream, cfg)  # NaN edges dropped == never matched
    got = substream_match(dirty, cfg, interpret=True, validate="sanitize")
    got_a = np.asarray(got.assigned)
    keep = np.ones(stream.num_edges, bool)
    keep[list(info.positions)] = False
    assert (got_a[keep] == np.asarray(want.assigned)[keep]).all()
    assert (got_a[~keep] == -1).all()


# ---------------------------------------------------------------------------
# 2. Plan/compile faults: the cascade degrades, observably, to a correct engine
# ---------------------------------------------------------------------------

MEGA = {"schedule": "mega"}
SEG1 = {"schedule": "mega", "seg_block": 1}

#: case -> (failing targets, engine options, calls that fail per target
#: (None: every call), the ladder rung that delivers)
PLAN_FAULTS = {
    "mega_plan": (("mega_plan",), MEGA, None, "waves_xla"),
    "mega_compile": (("mega_device",), MEGA, None, "waves_xla"),
    "mega_then_seg1": (("mega_plan",), MEGA, 1, "mega[seg_block=1]"),
    "all_pallas_mega": (("mega_plan", "mega_device"), MEGA, None, "waves_xla"),
    "down_to_scan": (
        ("mega_plan", "mega_device", "waves_xla"), MEGA, None, "scan",
    ),
    "seg1_plan": (
        ("mega_plan",), SEG1, 1, "mega[seg_block=1,tiles_per_block=1]",
    ),
    "seg1_compile": (("mega_device",), SEG1, None, "waves_xla"),
    "edges_compile": (("edges_device",), {"schedule": "edges"}, None, "waves_xla"),
}


def _faults(targets, times):
    if times is None:
        return faultline.failing(*targets)
    return faultline.flaky(*targets, times=times)


@pytest.mark.parametrize("name", sorted(PLAN_FAULTS))
def test_cascade_lands_on_a_correct_engine(name):
    targets, options, times, delivers = PLAN_FAULTS[name]
    stream, cfg = _stream(seed=1)
    want = mwm_scan(stream, cfg)
    tel = obs.Telemetry()
    with _faults(targets, times):
        got = substream_match(
            stream, cfg, interpret=True, on_plan_failure="fallback",
            telemetry=tel, **options,
        )
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()
    # degradation is observable, never silent
    assert tel.counters.get("fallback.count") >= 1
    events = [e for e in tel.events if e["name"] == "fallback"]
    assert events and all("reason" in e and "from_engine" in e for e in events)
    assert any(re.search("injected (failure|flake)", e["reason"]) for e in events)
    assert events[-1]["to_engine"] == delivers
    # the record of the engine that delivered carries the degradation depth
    if tel.match_calls:
        assert tel.match_calls[-1].counters["fallback.count"] == len(events)
    # the postcondition holds on what the cascade returned
    check_matching(got, stream, cfg)


def test_clean_path_records_zero_fallbacks():
    stream, cfg = _stream(seed=2)
    tel = obs.Telemetry()
    got = substream_match(
        stream, cfg, schedule="mega", interpret=True,
        on_plan_failure="fallback", telemetry=tel,
    )
    assert tel.counters.get("fallback.count") == 0
    assert not [e for e in tel.events if e["name"] == "fallback"]
    assert tel.match_calls[-1].engine == "pallas_mega"
    assert tel.match_calls[-1].counters["fallback.count"] == 0
    assert (
        np.asarray(got.assigned) == np.asarray(mwm_scan(stream, cfg).assigned)
    ).all()


def test_raise_mode_propagates_injected_failures():
    stream, cfg = _stream()
    with faultline.failing("mega_plan"):
        with pytest.raises(faultline.InjectedFailure, match="mega_plan"):
            substream_match(stream, cfg, schedule="mega", interpret=True)


def test_cascade_exhaustion_names_every_attempt():
    stream, cfg = _stream()
    all_engines = (
        "mega_plan", "mega_device", "edges_device", "waves_xla", "scan_oracle",
    )
    with faultline.failing(*all_engines):
        with pytest.raises(FallbackExhaustedError) as exc:
            substream_match(
                stream, cfg, schedule="mega", interpret=True,
                on_plan_failure="fallback",
            )
    labels = [label for label, _ in exc.value.attempts]
    assert labels == [
        "mega", "mega[seg_block=1]", "mega[seg_block=1,tiles_per_block=1]",
        "waves_xla", "scan",
    ]
    assert all("injected failure" in str(err) for _, err in exc.value.attempts)


def test_cascade_does_not_absorb_validation_errors():
    stream, cfg = _stream()
    dirty, _ = faultline.poison_ids(stream, cfg.n, (0,), "past_n")
    # a bad stream fails every engine identically; retrying would mask it
    with pytest.raises(StreamValidationError):
        substream_match(
            dirty, cfg, interpret=True, schedule="mega",
            on_plan_failure="fallback", validate="strict",
        )


@pytest.mark.parametrize("corruptor", ["truncate", "permute"])
def test_stale_schedule_is_rejected_then_survived(corruptor):
    stream, cfg = _stream(seed=3)
    src, dst, valid = (
        np.asarray(x) for x in (stream.src, stream.dst, stream.valid)
    )
    sch = wave_schedule(src, dst, valid=valid)
    bad = getattr(faultline, f"{corruptor}_schedule")(sch)
    with pytest.raises(ValueError):
        validate_schedule(bad, src, dst, valid)
    # raise mode: the corruption propagates
    with pytest.raises(ValueError):
        substream_match(stream, cfg, waves=bad, interpret=True, **SEG1)
    # fallback mode: every schedule consumer fails, scan (which ignores the
    # schedule) still delivers the bit-exact result
    tel = obs.Telemetry()
    got = substream_match(
        stream, cfg, waves=bad, interpret=True, on_plan_failure="fallback",
        telemetry=tel, **SEG1,
    )
    want = mwm_scan(stream, cfg)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()
    assert tel.counters.get("fallback.count") >= 1


def test_duplicate_order_entry_is_rejected():
    """An edge scheduled twice in DIFFERENT waves passes the coverage,
    slot-agreement and per-wave disjointness checks — only the
    order-is-a-permutation check stops it (it would double-count the
    edge in the gathered slot stream)."""
    stream, cfg = _stream(seed=5)
    src, dst, valid = (
        np.asarray(x) for x in (stream.src, stream.dst, stream.valid)
    )
    sch = wave_schedule(src, dst, valid=valid)
    bad = faultline.duplicate_order_entry(sch)
    with pytest.raises(ValueError, match="permutation"):
        validate_schedule(bad, src, dst, valid)


def test_fallback_result_repacked_to_requested_storage():
    stream, cfg = _stream(seed=4)
    with faultline.failing("mega_plan", "mega_device"):
        got = substream_match(
            stream, cfg, schedule="mega", interpret=True,
            on_plan_failure="fallback",
        )
    # the XLA fallbacks produce dense mb; the cascade honours the packed
    # contract of the engine the caller asked for
    assert got.is_packed
    want = mwm_scan(stream, cfg)
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


# ---------------------------------------------------------------------------
# 3. Result corruptions: check_matching flags every one
# ---------------------------------------------------------------------------


def _first_recorded(res):
    rec = np.nonzero(np.asarray(res.assigned) >= 0)[0]
    assert rec.size, "fixture must record at least one edge"
    return int(rec[0])


def _corrupt_out_of_range(res, stream, cfg):
    return corrupt_at(res, _first_recorded(res), cfg.L + 3), "range"


def _corrupt_negative(res, stream, cfg):
    return corrupt_at(res, _first_recorded(res), -5), "range"


def _corrupt_padding_record(res, stream, cfg):
    pad_pos = int(np.nonzero(~np.asarray(stream.valid))[0][0])
    return corrupt_at(res, pad_pos, 0), "padding"


def _corrupt_ineligible(res, stream, cfg):
    w = np.asarray(stream.weight)
    thr_top = float(np.asarray(cfg.thresholds())[-1])
    pos = np.nonzero(
        (np.asarray(stream.valid))
        & (w < thr_top)
        & (np.asarray(stream.src) != np.asarray(stream.dst))
    )[0]
    assert pos.size, "fixture must contain an edge below the top threshold"
    return corrupt_at(res, int(pos[0]), cfg.L - 1), "threshold"


def _corrupt_bit_cleared_packed(res, stream, cfg):
    p = _first_recorded(res)
    u = int(np.asarray(stream.src)[p])
    sub = int(np.asarray(res.assigned)[p])
    return (
        faultline.flip_matching_bit(faultline.repacked(res), u, sub),
        "matching bit",
    )


def _corrupt_bit_cleared_dense(res, stream, cfg):
    p = _first_recorded(res)
    v = int(np.asarray(stream.dst)[p])
    sub = int(np.asarray(res.assigned)[p])
    return faultline.flip_matching_bit(res, v, sub), "matching bit"


def corrupt_at(res, pos, value):
    return faultline.corrupt_assigned(res, pos, value)


RESULT_FAULTS = {
    "assigned_out_of_range": _corrupt_out_of_range,
    "assigned_negative": _corrupt_negative,
    "assigned_on_padding": _corrupt_padding_record,
    "assigned_ineligible": _corrupt_ineligible,
    "bit_cleared_packed": _corrupt_bit_cleared_packed,
    "bit_cleared_dense": _corrupt_bit_cleared_dense,
}


@pytest.mark.parametrize("fault", sorted(RESULT_FAULTS))
def test_check_matching_flags_every_result_corruption(fault):
    stream, cfg = _stream(seed=5, pad=4)
    res = mwm_scan(stream, cfg)
    check_matching(res, stream, cfg)  # clean baseline passes
    bad, needle = RESULT_FAULTS[fault](res, stream, cfg)
    with pytest.raises(MatchingInvariantError) as exc:
        check_matching(bad, stream, cfg)
    assert needle in str(exc.value)
    assert matching_problems(bad, stream, cfg)


def test_check_matching_flags_duplicate_substream_match():
    # equal-weight star: exactly one hub edge is recorded; duplicating its
    # substream onto a second hub edge breaks per-substream disjointness
    edges = [(0, i, 5.0) for i in range(1, 9)]
    src, dst, w = (np.asarray(x) for x in zip(*edges))
    stream = EdgeStream.from_numpy(src, dst, w)
    cfg = SubstreamConfig(n=9, L=8)
    res = mwm_scan(stream, cfg)
    p = _first_recorded(res)
    other = 1 if p != 1 else 2
    bad = faultline.corrupt_assigned(res, other, int(np.asarray(res.assigned)[p]))
    with pytest.raises(MatchingInvariantError) as exc:
        check_matching(bad, stream, cfg)
    assert "more than once" in str(exc.value)


def test_check_matching_flags_self_loop_record():
    stream, cfg = _stream(seed=6)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst).copy()
    loop_pos = 0
    dst[loop_pos] = src[loop_pos]
    loop_stream = EdgeStream(
        src=stream.src, dst=dst, weight=stream.weight, valid=stream.valid
    )
    res = mwm_scan(loop_stream, cfg)
    bad = faultline.corrupt_assigned(res, loop_pos, 0)
    with pytest.raises(MatchingInvariantError) as exc:
        check_matching(bad, loop_stream, cfg)
    assert "self-loop" in str(exc.value)


def test_check_matching_covers_the_merge():
    stream, cfg = _stream(seed=7)
    res = mwm_scan(stream, cfg)
    merged = merge_host(stream, res, cfg)
    check_matching(res, stream, cfg, merged=merged)  # clean merge passes
    if merged.size:
        dup = np.concatenate([merged, merged[:1]])
        assert any(
            "twice" in p for p in matching_problems(res, stream, cfg, merged=dup)
        )
    unrecorded = np.nonzero(np.asarray(res.assigned) < 0)[0][:1]
    bad = np.concatenate([merged, unrecorded])
    assert any(
        "never recorded" in p
        for p in matching_problems(res, stream, cfg, merged=bad)
    )
    # a wildly better "exact" optimum violates the (4+eps) bound
    problems = matching_problems(
        res, stream, cfg, merged=merged, exact_weight=1e9
    )
    assert any("bound" in p or "exact" in p for p in problems)

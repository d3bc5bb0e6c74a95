"""Smoke run of the substream-matching path on a TPU.

Builds the paper's smallest evaluated workload from its seed (§5, the
settings of ``src/repro/configs/paper_matching.py`` at scale 16: a
Kronecker graph with n = 65,536 and edge factor 48, L = 64, eps = 0.1,
weights U[1, (1+eps)^(L-1)+1]) and drives the matching entry points on
the chip, each to ``block_until_ready``:

* ``mwm_pipeline(part1="pallas")`` — the per-edge kernel in blocked
  order, then the Part 2 merge;
* ``substream_match(schedule="mega")`` at ``seg_block=1`` (one segment
  per tile) and at the default ``seg_block``;
* ``match_epochs(engine="mega", epochs=4)``.

Every Part 1 result (``assigned`` and ``mb``) must be bit-identical to
``mwm_scan`` run on the same chip, and every merged matching must pass
``check_matching``. The engines run compiled with
``on_plan_failure="raise"``; a ``fallback`` telemetry event or an
interpreted call fails the run. Earlier lines print smoke timings
(compile + first call, and a steady call, in wall seconds on the host
clock) — a bring-up check, not a benchmark. The last line is one JSON
object naming the device.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # mwm_rounds_sharded on a 2x2 mesh
                                     # against mwm_scan, nothing else

Exits non-zero, before printing the JSON line, when JAX finds no TPU or
when the repository is not next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SMOKE_SCALE = 16  # the paper's smallest evaluated Kronecker graph
EPOCHS = 4


class SmokeFailure(Exception):
    pass


def workload(scale: int, edge_multiple: int = 1):
    """The paper workload at ``scale``, padded with invalid edges to a
    multiple of ``edge_multiple`` (the sharded path splits the stream
    evenly)."""
    from repro.configs.paper_matching import CONFIG
    from repro.core import EdgeStream, SubstreamConfig
    from repro.graph.generators import kronecker_graph, uniform_weights

    wl = dataclasses.replace(CONFIG, scale=scale)
    src, dst = kronecker_graph(wl.scale, wl.edge_factor, seed=wl.seed)
    w = uniform_weights(len(src), wl.L, wl.eps, seed=wl.seed)
    m_pad = -(-len(src) // edge_multiple) * edge_multiple
    stream = EdgeStream.from_numpy(src, dst, w, n_pad=m_pad)
    cfg = SubstreamConfig(n=1 << wl.scale, L=wl.L, eps=wl.eps)
    return wl, stream, cfg


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _same(name, got, want):
    import numpy as np

    if not np.array_equal(np.asarray(got.assigned), np.asarray(want.assigned)):
        raise SmokeFailure(f"{name}: assigned differs from mwm_scan")
    if not np.array_equal(np.asarray(got.mb), np.asarray(want.mb)):
        raise SmokeFailure(f"{name}: matching bits differ from mwm_scan")


def _check_telemetry(name, tel, expect_compiled: bool):
    """No fallback, and every kernel call ran on the backend asked for."""
    fallbacks = [e for e in tel.events if e["name"] == "fallback"]
    if fallbacks:
        raise SmokeFailure(f"{name}: fallback events {fallbacks}")
    backends = [e for e in tel.events if e["name"] == "substream_match.backend"]
    calls = list(tel.match_calls)
    if not calls:
        raise SmokeFailure(f"{name}: no instrumented kernel call recorded")
    if expect_compiled:
        bad = [e for e in backends if e["interpret"] or e["backend"] != "tpu"]
        bad += [c.engine for c in calls if c.interpret or c.backend != "tpu"]
        if bad:
            raise SmokeFailure(f"{name}: calls not compiled on the TPU: {bad}")


def _report(name, first_s, steady_s, tel, steady_calls):
    recs = list(tel.match_calls)[-steady_calls:]
    host = {
        k: sum(r.stage_seconds.get(k, 0.0) for r in recs)
        for k in ("schedule", "pack", "layout")
    }
    vmem = max(int(r.counters.get("plan.bit_block_bytes", 0)) for r in recs)
    print(
        f"smoke-timing (not a benchmark) engine={name} "
        f"compile_plus_first_s={first_s:.3f} steady_s={steady_s:.3f} "
        f"host_schedule_s={host['schedule'] + host['pack']:.3f} "
        f"host_layout_s={host['layout']:.3f} plan_vmem_bit_block_bytes={vmem}",
        flush=True,
    )


def run_one_chip(scale: int = SMOKE_SCALE, expect_compiled: bool = True):
    """The one-chip phases. Raises :class:`SmokeFailure` on any mismatch."""
    from repro import obs
    from repro.core import (
        check_matching,
        merge_host,
        mwm_blocked,
        mwm_pipeline,
        mwm_scan,
    )
    from repro.kernels.substream_match.ops import match_epochs, substream_match

    t0 = time.perf_counter()
    wl, stream, cfg = workload(scale)
    print(
        f"workload scale={wl.scale} n={cfg.n} m={stream.num_edges} "
        f"L={cfg.L} eps={cfg.eps} built_s={time.perf_counter() - t0:.3f}",
        flush=True,
    )
    ref, t_ref = _timed(lambda: mwm_scan(stream, cfg))
    ref_blocked, t_refb = _timed(
        lambda: mwm_blocked(stream, cfg, K=wl.K, backend="scan")
    )
    print(
        f"smoke-timing (not a benchmark) reference=mwm_scan "
        f"stream_order_s={t_ref:.3f} blocked_order_s={t_refb:.3f}",
        flush=True,
    )
    strict = dict(on_plan_failure="raise")

    # per-edge kernel, blocked order; then the end-to-end pipeline
    tel = obs.Telemetry()
    run = lambda: mwm_blocked(  # noqa: E731
        stream, cfg, K=wl.K, backend="pallas", telemetry=tel, **strict
    )
    _, first = _timed(run)
    got, steady = _timed(run)
    _report("edges", first, steady, tel, 1)
    _same("pallas edges (blocked)", got, ref_blocked)
    (idx, weight), t_pipe = _timed(
        lambda: mwm_pipeline(
            stream, cfg, part1="pallas", K=wl.K, telemetry=tel, **strict
        )
    )
    print(
        f"smoke-timing (not a benchmark) engine=mwm_pipeline[pallas] "
        f"wall_s={t_pipe:.3f} merged_edges={len(idx)} weight={weight:.6g}",
        flush=True,
    )
    check_matching(got, stream, cfg, merged=idx)
    want_idx = merge_host(stream, ref_blocked, cfg)
    if sorted(idx.tolist()) != sorted(want_idx.tolist()):
        raise SmokeFailure("mwm_pipeline(part1='pallas'): merge differs")
    _check_telemetry("edges", tel, expect_compiled)

    want_idx = merge_host(stream, ref, cfg)
    for name, seg_block in (("mega[seg_block=1]", 1), ("mega", None)):
        tel = obs.Telemetry()
        run = lambda: substream_match(  # noqa: E731
            stream, cfg, schedule="mega", seg_block=seg_block, telemetry=tel,
            **strict,
        )
        _, first = _timed(run)
        got, steady = _timed(run)
        _report(name, first, steady, tel, 1)
        _same(name, got, ref)
        idx = merge_host(stream, got, cfg)
        check_matching(got, stream, cfg, merged=idx)
        _check_telemetry(name, tel, expect_compiled)

    tel = obs.Telemetry()
    run = lambda: match_epochs(  # noqa: E731
        stream, cfg, epochs=EPOCHS, engine="mega", telemetry=tel, **strict
    )
    _, first = _timed(run)
    got, steady = _timed(run)
    _report(f"match_epochs[mega,epochs={EPOCHS}]", first, steady, tel, EPOCHS)
    _same("match_epochs[mega]", got, ref)
    idx = merge_host(stream, got, cfg)
    check_matching(got, stream, cfg, merged=idx)
    if sorted(idx.tolist()) != sorted(want_idx.tolist()):
        raise SmokeFailure("match_epochs[mega]: merge differs")
    _check_telemetry("match_epochs[mega]", tel, expect_compiled)


def run_four_chips(scale: int = SMOKE_SCALE, devices=None):
    """``mwm_rounds_sharded`` on a 2x2 (data, model) mesh over four
    devices, against ``mwm_scan``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import (
        MatchingResult,
        check_matching,
        merge_host,
        mwm_rounds_sharded,
        mwm_scan,
    )

    devices = list(devices if devices is not None else jax.devices())[:4]
    if len(devices) < 4:
        raise SmokeFailure(f"--chips 4 needs four devices, found {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    t0 = time.perf_counter()
    wl, stream, cfg = workload(scale, edge_multiple=2)
    print(
        f"workload scale={wl.scale} n={cfg.n} m={stream.num_edges} "
        f"L={cfg.L} eps={cfg.eps} built_s={time.perf_counter() - t0:.3f} "
        f"mesh=2x2(data,model)",
        flush=True,
    )
    ref, t_ref = _timed(lambda: mwm_scan(stream, cfg))
    run = lambda: mwm_rounds_sharded(stream, cfg, mesh)  # noqa: E731
    _, first = _timed(run)
    (assigned, mb), steady = _timed(run)
    got = MatchingResult(assigned=assigned, mb=mb)
    _same("mwm_rounds_sharded", got, ref)
    idx = merge_host(stream, got, cfg)
    check_matching(got, stream, cfg, merged=idx)
    print(
        f"smoke-timing (not a benchmark) engine=mwm_rounds_sharded "
        f"compile_plus_first_s={first:.3f} steady_s={steady:.3f} "
        f"reference_mwm_scan_s={t_ref:.3f} merged_edges={len(idx)}",
        flush=True,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: the one-chip engines; 4: only mwm_rounds_sharded on 2x2",
    )
    args = parser.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: FAIL: the repository is not next to this file",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: {args.chips} chips asked, "
              f"{len(devices)} found", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
    except Exception as err:  # noqa: BLE001 — report any phase failure, exit 1
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAIL: {type(err).__name__}: {err}", file=sys.stderr)
        return 1
    print(f"smoke total_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

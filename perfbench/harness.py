"""Run one cell of ``BENCHMARK.json`` once and print its result line.

A run builds the cell's inputs from ``--seed``, warms up with one
untimed job (set-up, with start-up and compilation), then runs jobs
back to back, one client in a closed loop, until ``--seconds`` have
passed; the job in flight at that moment is waited for, and the window
ends when it returns. Edges per second are the edges of every job in
the window over the window's length. With ``--trace 1`` the window runs
under the JAX profiler and the program's telemetry, and the run reports
the cell's per-layer metrics instead of its end-to-end ones.

After the window, with the device memory peak read and the program's
arrays dropped, the plain reference (:mod:`perfbench.reference`) solves
the same stream on the host and every answer of the window is compared
with it. The numbers compared, each beside its limit, are printed last
on standard error and under the result line's last key, ``check``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import hashlib
import importlib
import json
import pathlib
import shutil
import sys
import time
import traceback

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: Inputs, compiled programs and traces of earlier runs in this
#: checkout (git-ignored). The path is fixed: it is part of the key of
#: JAX's persistent compilation cache.
CACHE = ROOT / ".perfbench_cache"
#: Limits of the numbers compared: every answer is exactly the
#: reference's, so every count of differences is held to 0. The weight
#: gap allows float32 rounding of the matching's sum.
LIMITS = {"assigned_diff": 0, "state_diff": 0, "merged_diff": 0, "weight_gap": 1e-6}


class RunError(Exception):
    """The run cannot start: a missing file, chip or table entry."""


@dataclasses.dataclass
class Workload:
    name: str
    n: int
    L: int
    eps: float
    K: int
    src: np.ndarray  # int32 [m], host
    dst: np.ndarray  # int32 [m], host
    weight: np.ndarray  # float32 [m], host
    cfg: object = None  # the program's SubstreamConfig

    @property
    def m(self) -> int:
        return int(self.src.size)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, workload: str, root: pathlib.Path = ROOT):
    """The cell entry, its configuration file and traffic file, and the
    end-to-end and per-layer metric entries it reports."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return cell, config, traffic, mine(spec["end_to_end"]), mine(spec["per_layer"])


# ---------------------------------------------------------------- inputs


def graph(config: dict, cache: pathlib.Path | None = CACHE):
    """The configuration's graph, generated once per checkout and then
    read from the cache (keyed by the generator's parameters)."""
    params = config["graph"]
    key = hashlib.sha256(json.dumps(params, sort_keys=True).encode()).hexdigest()[:16]
    path = None if cache is None else cache / "graphs" / f"{config['name']}-{key}.npz"
    if path is not None and path.exists():
        with np.load(path) as z:
            return z["src"], z["dst"]
    family = importlib.import_module(f"perfbench.graphs.{params['family']}")
    src, dst = family.generate(params)
    if path is not None:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npz")
        np.savez(tmp, src=src, dst=dst)
        tmp.replace(path)
    return src, dst


def make_workload(config: dict, seed: int, cache: pathlib.Path | None = CACHE) -> Workload:
    """The stream of one run: the configuration's graph with its vertex
    labels permuted and its edges shuffled (where the configuration
    says so) and weights drawn U[1, (1+eps)^(L-1) + 1], all from
    ``seed``. Every seed gives the same graph, so the same sizes."""
    src, dst = graph(config, cache)
    n, L, eps = int(config["graph"]["n"]), int(config["L"]), float(config["eps"])
    rng = np.random.default_rng(np.random.SeedSequence(abs(int(seed))))
    if config["permute_vertices"]:
        label = rng.permutation(n).astype(np.int32)
        src, dst = label[src], label[dst]
    if config["shuffle_edges"]:
        order = rng.permutation(src.size)
        src, dst = src[order], dst[order]
    hi = (1.0 + eps) ** (L - 1) + 1.0
    weight = rng.uniform(1.0, hi, src.size).astype(np.float32)
    return Workload(
        name=config["name"], n=n, L=L, eps=eps, K=int(config["K"]),
        src=np.ascontiguousarray(src, np.int32), dst=np.ascontiguousarray(dst, np.int32),
        weight=weight,
    )


# ---------------------------------------------------------------- window


class JobContext:
    """What an entry's job may use: the telemetry session, the
    benchmark's own trace annotations, and the epoch latency list."""

    def __init__(self, telemetry, annotate: bool):
        self.telemetry = telemetry
        self.annotate = annotate
        self.epoch_seconds: list[float] = []
        self._open = []

    def mark(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("pb." + name)

    def begin_mark(self, name: str):
        cm = self.mark(name)
        cm.__enter__()
        self._open.append(cm)

    def end_mark(self):
        if self._open:
            self._open.pop().__exit__(None, None, None)


def _host_answer(ans):
    """Copy an answer's device arrays to the host."""
    from perfbench.reference import Answer

    return Answer(
        merged=np.asarray(ans.merged, np.int64),
        weight=float(ans.weight),
        assigned=None if ans.assigned is None else np.asarray(ans.assigned),
        state=None if ans.state is None else np.asarray(ans.state),
    )


def _same(a, b) -> bool:
    def eq(x, y):
        return (x is None and y is None) or (
            x is not None and y is not None and np.array_equal(x, y)
        )

    return (
        a.weight == b.weight and eq(a.merged, b.merged)
        and eq(a.assigned, b.assigned) and eq(a.state, b.state)
    )


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    jobs: int = 0
    attempted: int = 0
    errors: int = 0
    #: distinct answers, each with the number of jobs that gave it
    answers: list = dataclasses.field(default_factory=list)


def run_window(entry, wl, traffic, seconds: float, ctx: JobContext) -> Window:
    """Jobs back to back until ``seconds`` have passed; the last one
    started is waited for."""
    win = Window()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with ctx.mark("window"):
        while time.perf_counter() < deadline:
            win.attempted += 1
            try:
                with ctx.mark("job"):
                    ans = entry.job(wl, traffic, ctx)
            except Exception:  # noqa: BLE001 — a failed job is counted and reported
                traceback.print_exc()
                win.errors += 1
                break
            win.jobs += 1
            ans = _host_answer(ans)
            for seen in win.answers:
                if _same(seen[0], ans):
                    seen[1] += 1
                    break
            else:
                win.answers.append([ans, 1])
        win.seconds = time.perf_counter() - t0
    return win


# ---------------------------------------------------------------- checks


def check(entry, wl, answers) -> tuple[dict, int]:
    """Solve the stream with the reference and compare every distinct
    answer. Returns the worst reading of each number and the number of
    jobs whose answer differs from the reference."""
    from perfbench import reference

    order = reference.blocked_order(wl.src, wl.dst, wl.K) if entry.ORDER == "blocked" else None
    want = reference.solve(wl.src, wl.dst, wl.weight, wl.n, wl.L, wl.eps, order=order)
    worst: dict = {}
    wrong = 0
    for ans, count in answers:
        got = reference.compare(ans, want)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
        if any(v > LIMITS[k] for k, v in got.items()):
            wrong += count
    return worst, wrong


# ---------------------------------------------------------------- the run


def _compile_counter():
    """Counts JAX tracing and backend compilations from now on."""
    import jax

    seen = {"n": 0}

    def listener(event, duration, **kw):
        if event.endswith(("backend_compile_duration", "jaxpr_trace_duration")):
            seen["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def run_cell(
    config: dict, traffic: dict, e2e: list, layer: list, seed: int, seconds: float,
    trace: bool, t_start: float, cache: pathlib.Path | None = CACHE, entry=None,
) -> tuple[dict, list[str]]:
    """One run of a cell on whatever devices JAX has. Returns the result
    object and the check lines. ``entry`` replaces the traffic's entry
    module (the control and the tests put another job in its place)."""
    import jax
    from repro import obs
    from repro.core import SubstreamConfig

    devices = jax.devices()
    peak = None
    if trace:
        from perfbench import roofline

        peak = roofline.peaks(devices[0].device_kind)
    if entry is None:
        entry = importlib.import_module(f"perfbench.entries.{traffic['entry']}")
    if traffic.get("loop", "closed") != "closed" or traffic.get("clients", 1) != 1:
        raise RunError("the harness drives one client in a closed loop")
    wl = make_workload(config, seed, cache)
    wl.cfg = SubstreamConfig(n=wl.n, L=wl.L, eps=wl.eps)

    def session():
        return obs.Telemetry() if trace else obs.DISABLED

    entry.job(wl, traffic, JobContext(session(), annotate=False))  # warm-up
    setup_s = time.perf_counter() - t_start

    ctx = JobContext(session(), annotate=trace)
    trace_dir = None
    if trace:
        trace_dir = (cache or HERE) / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles = _compile_counter()
    win = run_window(entry, wl, traffic, seconds, ctx)
    compiles_in_window = compiles["n"]
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from perfbench import trace as trace_mod

        timeline = trace_mod.load(trace_dir)
        (trace_dir / "timeline.json").write_text(timeline.to_json())
        reduced = trace_mod.reduce(timeline)
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    gc.collect()

    worst, wrong = check(entry, wl, win.answers) if win.answers else ({}, 0)
    failed = win.errors + wrong
    correct = win.jobs > 0 and failed == 0 and all(
        v <= LIMITS[k] for k, v in worst.items()
    )

    metrics = {}
    if not trace:
        values = {
            "edges_per_s": win.jobs * wl.m / win.seconds,
            "setup_s": setup_s,
        }
        if ctx.epoch_seconds:
            values["epoch_p95_ms"] = float(np.percentile(ctx.epoch_seconds, 95)) * 1e3
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        lctx = LayerContext(
            trace=reduced, telemetry=ctx.telemetry, jobs=win.jobs, workload=wl, peak=peak
        )
        for m in layer:
            reader = importlib.import_module(f"perfbench.metrics.{m['name']}")
            value = reader.read(lctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result = {
        "correct": bool(correct),
        "attempted": win.attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps
        }
    result["check"] = {k: {"value": v, "limit": LIMITS[k]} for k, v in worst.items()}
    result["check"]["failed_jobs"] = {"value": failed, "limit": 0}
    lines = [
        f"window_s={win.seconds} jobs={win.jobs} distinct_answers={len(win.answers)} "
        f"compiles_in_window={compiles_in_window} edges_per_job={wl.m} "
        f"epochs={len(ctx.epoch_seconds)}"
    ] + [f"check {k} value={v['value']} limit={v['limit']}" for k, v in result["check"].items()]
    return result, lines


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader (``metrics/<name>.py``, function
    ``read(ctx) -> float | None``) is given: the reduced device trace of
    the window, the program's telemetry session, the number of jobs in
    the window, the workload and the device's peak row."""

    trace: object
    telemetry: object
    jobs: int
    workload: Workload
    peak: dict


# ---------------------------------------------------------------- main


def _configure_jax(cache: pathlib.Path):
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="Run one benchmark cell once on the chip.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        spec = load_spec()
        cell, config, traffic, e2e, layer = cell_parts(spec, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise RunError(f"the program is not in this checkout ({ROOT / 'src' / 'repro'})")
        sys.path.insert(0, str(ROOT / "src"))
        import jax

        _configure_jax(CACHE)
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise RunError(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell["chips"]:
            raise RunError(f"{cell['chips']} chips asked, {len(devices)} found")
        result, lines = run_cell(
            config, traffic, e2e, layer, args.seed, args.seconds, bool(args.trace), t_start
        )
    except (RunError, OSError, KeyError) as err:
        print(f"perfbench: {type(err).__name__}: {err}", file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

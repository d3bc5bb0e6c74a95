"""The program's own spans, read beside the benchmark's trace.

While its telemetry is on, every span the program records
(``repro.obs``) is both an event of the telemetry session and a
``jax.profiler.TraceAnnotation`` named ``repro.<span>`` in the
profiler's host plane, on the clock of the device operations. Two
readers use them:

* :func:`ms_per_job` sums the session's events of some span names per
  job: the per-layer metrics ``schedule_assign_ms``,
  ``schedule_pack_ms``, ``slot_layout_ms`` and ``host_copy_ms``;
* :func:`load` and :func:`idle_by_stage` split the traced window's idle
  device time by the innermost annotation of either kind, the
  benchmark's (``pb.*``, printed without the prefix as in
  ``perfbench.trace``) or the program's (``repro.*``, printed whole).
  Each idle interval is cut at every annotation boundary inside it, and
  each piece is credited to the innermost annotation around it.

``python3 -m perfbench.spans [trace dir]`` prints that split for the
last traced run of this checkout (``.perfbench_cache/trace``), with the
program spans' summed host seconds in the window, as one JSON line.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import sys

from perfbench import trace

PREFIX = "repro."
#: Where the harness leaves the last traced run's profile.
CACHE_TRACE = pathlib.Path(__file__).resolve().parents[1] / ".perfbench_cache" / "trace"


def ms_per_job(ctx, names) -> float | None:
    """Summed duration of the session's spans named in ``names``, in ms
    per job of the window; None where there is none (a program that
    records no such span)."""
    durs = [
        ev["dur"] for ev in ctx.telemetry.tracer.events
        if ev.get("ph") == "X" and ev["name"] in names
    ]
    if not durs or not ctx.jobs:
        return None
    return sum(durs) / ctx.jobs / 1e3


def load(log_dir) -> list[tuple[str, int, int]]:
    """The program's annotations ``[(name, start_ns, end_ns)]`` in the
    newest ``.xplane.pb`` under ``log_dir`` (the file ``trace.load``
    reads)."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans.extend(
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for ev in line.events if ev.name.startswith(PREFIX)
            )
    return spans


def _window(tl: trace.Timeline) -> tuple[int, int]:
    windows = [(s, e) for name, s, e in tl.marks if name == trace.WINDOW_MARK]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_MARK} annotation, found {len(windows)}")
    return windows[0]


def _name(annotation: str) -> str:
    if annotation.startswith(trace.MARK_PREFIX):
        return annotation[len(trace.MARK_PREFIX):]
    return annotation


def _innermost(annotations, t) -> str:
    best = None
    for name, s, e in annotations:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return _name(best[0]) if best else "outside"


def idle_by_stage(tl: trace.Timeline, spans, top: int | None = None) -> list[list]:
    """Idle device seconds of the window by innermost annotation,
    ``[[label, seconds]]`` largest first, averaged over the devices."""
    lo, hi = _window(tl)
    planes = sorted(tl.device_ops)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    annotations = [
        (name, s, e) for name, s, e in list(tl.marks) + list(spans)
        if name != trace.WINDOW_MARK and e > lo and s < hi
    ]
    bounds = sorted({t for _, s, e in annotations for t in (s, e)})
    idle: dict[str, int] = {}
    for plane in planes:
        ops = [(max(s, lo), min(e, hi)) for _, s, e, _ in tl.device_ops[plane] if e > lo and s < hi]
        edges = [lo] + [t for iv in trace.union(ops) for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            cuts = [s] + bounds[bisect.bisect_right(bounds, s):bisect.bisect_left(bounds, e)] + [e]
            for a, b in zip(cuts, cuts[1:]):
                label = _innermost(annotations, (a + b) // 2)
                idle[label] = idle.get(label, 0) + (b - a)
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / len(planes) / 1e9] for k, v in ranked]


def host_seconds(tl: trace.Timeline, spans) -> dict[str, float]:
    """Summed host seconds of each program span name inside the window;
    a ``<copy> in <span>`` key holds the part of a ``repro.copy.*`` name
    that lies inside another program span (``repro.pallas_mega.layout``:
    the copies that ``host_schedule_ms`` counts)."""
    lo, hi = _window(tl)
    inside = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    copies = f"{PREFIX}copy."
    outer = [(n, s, e) for n, s, e in inside if not n.startswith(copies)]
    out: dict[str, float] = {}

    def add(key, s, e):
        out[key] = out.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9

    for name, s, e in inside:
        add(name, s, e)
        if name.startswith(copies):
            for span, a, b in outer:
                if a <= s and e <= b:
                    add(f"{name} in {span}", s, e)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    log_dir = pathlib.Path(argv[0]) if argv else CACHE_TRACE
    tl = trace.load(log_dir)
    spans = load(log_dir)
    r = trace.reduce(tl)
    print(json.dumps({
        "window_s": r.window_s,
        "idle_s": r.window_s - r.busy_s,
        "idle_gaps": idle_by_stage(tl, spans),
        "span_seconds": host_seconds(tl, spans),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

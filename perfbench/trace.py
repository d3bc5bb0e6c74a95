"""Reduce a profiler trace of the measured window to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Timeline`: the device operations of every TPU (name, start, end
in nanoseconds on the profiler's clock) and the benchmark's own host
annotations (``pb.*``). ``reduce`` then gives, inside the annotation
``pb.window``:

* ``busy_s``: the union of each device's operation intervals, averaged
  over the devices; ``window_s`` the window's length;
* ``op_seconds`` / ``op_counts``: device time and event count of each
  operation name (summed over the devices); ``seconds_of(text)`` the
  device time of the ops whose name or framework name holds ``text``,
  which the kernel metrics read;
* ``device_ops``: the ten operations with the most device time;
* ``idle_gaps``: idle device time by what the host was doing, each gap
  credited to the innermost benchmark annotation around its midpoint.

A Timeline round-trips through JSON (``to_json``/``from_json``), which
is the form of the recorded test fixture.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re

MARK_PREFIX = "pb."
WINDOW_MARK = "pb.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: Event stats that name the framework op behind a device op.
DETAIL_STATS = ("tf_op", "long_name")


@dataclasses.dataclass
class Timeline:
    #: per device plane: [(op name, start_ns, end_ns, framework name)]
    device_ops: dict[str, list[tuple[str, int, int, str]]]
    #: benchmark annotations: [(name, start_ns, end_ns)]
    marks: list[tuple[str, int, int]]

    def to_json(self) -> str:
        return json.dumps(
            {"device_ops": self.device_ops, "marks": self.marks}, separators=(",", ":")
        )

    @staticmethod
    def from_json(text: str) -> "Timeline":
        raw = json.loads(text)
        return Timeline(
            device_ops={k: [tuple(e) for e in v] for k, v in raw["device_ops"].items()},
            marks=[tuple(e) for e in raw["marks"]],
        )


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    op_seconds: dict[str, float]
    op_counts: dict[str, int]
    device_ops: list[list]
    idle_gaps: list[list]
    #: (name, framework name, seconds) of every op inside the window
    ops: list = dataclasses.field(default_factory=list)

    def seconds_of(self, text: str):
        """Device seconds of the ops whose name or framework name holds
        ``text``, or None where none does."""
        hits = [d for name, detail, d in self.ops if text in name or text in detail]
        return sum(hits) if hits else None


def load(log_dir) -> Timeline:
    """The newest ``.xplane.pb`` under ``log_dir`` as a Timeline."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device_ops: dict[str, list] = {}
    marks = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         _detail(ev))
                        for ev in line.events
                    )
        else:
            for line in plane.lines:
                marks.extend(
                    (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(MARK_PREFIX)
                )
    return Timeline(device_ops=device_ops, marks=marks)


def _detail(ev) -> str:
    stats = dict(ev.stats)
    return " ".join(str(stats[k])[:300] for k in DETAIL_STATS if stats.get(k))


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _label(marks, t):
    """The innermost benchmark annotation that covers time ``t``."""
    best = None
    for name, s, e in marks:
        if s <= t <= e and name != WINDOW_MARK and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(MARK_PREFIX):] if best else "outside"


def reduce(tl: Timeline, top: int = 10) -> Reduced:
    windows = [(s, e) for name, s, e in tl.marks if name == WINDOW_MARK]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_MARK} annotation, found {len(windows)}")
    lo, hi = windows[0]
    planes = sorted(tl.device_ops)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns = 0
    gaps: dict[str, int] = {}
    op_ns: dict[str, int] = {}
    op_n: dict[str, int] = {}
    inside = []
    for plane in planes:
        ops = [(n, s, e, x) for n, s, e, x in tl.device_ops[plane] if e > lo and s < hi]
        for name, s, e, detail in ops:
            d = min(e, hi) - max(s, lo)
            op_ns[name] = op_ns.get(name, 0) + d
            op_n[name] = op_n.get(name, 0) + 1
            inside.append((name, detail, d / 1e9))
        busy = union(_clip([(s, e) for _, s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = _label(tl.marks, (s + e) // 2)
                gaps[label] = gaps.get(label, 0) + (e - s)
    n_dev = len(planes)

    def ranked(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n_dev / 1e9] for k, v in items]

    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / n_dev / 1e9,
        devices=n_dev,
        op_seconds={k: v / 1e9 for k, v in op_ns.items()},
        op_counts=op_n,
        device_ops=ranked(op_ns),
        idle_gaps=ranked(gaps),
        ops=inside,
    )

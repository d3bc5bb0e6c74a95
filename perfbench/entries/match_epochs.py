"""The stream replayed as an arriving edge log: ``match_epochs`` over
the traffic's ``epochs`` equal slices with the carried state, closed
loop, then ``merge_host``. An epoch's latency runs from the previous
epoch's state on the host (for the first, from the call) to its own."""
from __future__ import annotations

import time

import numpy as np

from perfbench.reference import Answer

ORDER = "stream"


def job(wl, params, ctx) -> Answer:
    from repro.core import EdgeStream, merge_host, matching_weight
    from repro.kernels.substream_match import ops

    with ctx.mark("from_numpy"):
        stream = EdgeStream.from_numpy(wl.src, wl.dst, wl.weight)
    marks = []

    def hook(k, state):
        marks.append(time.perf_counter())
        ctx.end_mark()
        if k + 1 < params["epochs"]:
            ctx.begin_mark("epoch")

    with ctx.mark("engine"):
        marks.append(time.perf_counter())
        ctx.begin_mark("epoch")
        res = ops.match_epochs(
            stream, wl.cfg, epochs=params["epochs"], engine=params["engine"],
            on_plan_failure="raise", telemetry=ctx.telemetry, epoch_hook=hook,
        )
    ctx.epoch_seconds.extend(np.diff(marks).tolist())
    with ctx.mark("merge"):
        merged = merge_host(stream, res, wl.cfg, telemetry=ctx.telemetry)
        weight = matching_weight(stream, merged)
    return Answer(
        merged=merged, weight=weight, assigned=res.assigned, state=res.mb_packed,
    )

"""Whole-graph job: ``EdgeStream.from_numpy``, ``substream_match`` with
the traffic's ``schedule``, then ``merge_host``."""
from __future__ import annotations

from perfbench.reference import Answer

ORDER = "stream"


def job(wl, params, ctx) -> Answer:
    from repro.core import EdgeStream, merge_host, matching_weight
    from repro.kernels.substream_match import ops

    with ctx.mark("from_numpy"):
        stream = EdgeStream.from_numpy(wl.src, wl.dst, wl.weight)
    with ctx.mark("engine"):
        res = ops.substream_match(
            stream, wl.cfg, schedule=params["schedule"],
            on_plan_failure="raise", telemetry=ctx.telemetry,
        )
    with ctx.mark("merge"):
        merged = merge_host(stream, res, wl.cfg, telemetry=ctx.telemetry)
        weight = matching_weight(stream, merged)
    return Answer(
        merged=merged, weight=weight, assigned=res.assigned, state=res.mb_packed,
    )

"""Whole-graph job through the public end-to-end entry
``mwm_pipeline(part1=...)``: for ``"pallas"`` the blocked (Listing 2)
order, the per-edge kernel, then the host merge. The entry hands back
only the merged matching and its weight, so Part 1 is compared through
them."""
from __future__ import annotations

from perfbench.reference import Answer

ORDER = "blocked"


def job(wl, params, ctx) -> Answer:
    import repro.core as core

    with ctx.mark("from_numpy"):
        stream = core.EdgeStream.from_numpy(wl.src, wl.dst, wl.weight)
    with ctx.mark("engine"):
        merged, weight = core.mwm_pipeline(
            stream, wl.cfg, part1=params["part1"], K=wl.K,
            on_plan_failure="raise", telemetry=ctx.telemetry,
        )
    return Answer(merged=merged, weight=weight)

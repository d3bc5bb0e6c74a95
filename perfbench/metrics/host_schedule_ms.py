"""Host wave schedule and slot layout per job, in ms: the program's
telemetry stages ``schedule + pack + layout`` summed over the job's
engine calls. None where no call scheduled anything (the per-edge
engine has no schedule)."""

STAGES = ("schedule", "pack", "layout")


def read(ctx):
    total = sum(
        call.stage_seconds.get(k, 0.0)
        for call in ctx.telemetry.match_calls for k in STAGES
    )
    if total <= 0 or not ctx.jobs:
        return None
    return total / ctx.jobs * 1e3

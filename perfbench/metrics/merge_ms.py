"""Part 2 merge per job, in ms: the program's ``merge.host`` spans. None
where the entry merged without telemetry."""

SPAN = "merge.host"


def read(ctx):
    durs = [
        ev["dur"] for ev in ctx.telemetry.tracer.events
        if ev["name"] == SPAN and ev.get("ph") == "X"
    ]
    if not durs or not ctx.jobs:
        return None
    return sum(durs) / ctx.jobs / 1e3

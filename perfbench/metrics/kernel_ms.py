"""Device time of the matching kernel per job, in ms: the summed device
durations of the trace events of the pallas_call named
``substream_match``, over the jobs of the window."""

KERNEL = "substream_match"


def read(ctx):
    s = ctx.trace.seconds_of(KERNEL)
    if s is None or not ctx.jobs:
        return None
    return s / ctx.jobs * 1e3

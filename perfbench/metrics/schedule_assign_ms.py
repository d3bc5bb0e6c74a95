"""Host wave-schedule assignment per job, in ms: the program's
``wave_schedule.assign`` spans (the conflict-depth pass of
``repro.graph.waves``, the ``schedule`` stage). None where no job built
a schedule."""
from perfbench.spans import ms_per_job

SPANS = ("wave_schedule.assign",)


def read(ctx):
    return ms_per_job(ctx, SPANS)

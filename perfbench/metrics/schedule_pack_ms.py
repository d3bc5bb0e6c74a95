"""Host fill-packing of the wave schedule per job, in ms: the program's
``wave_schedule.pack`` spans (the ``pack`` stage). None where no job
built a schedule."""
from perfbench.spans import ms_per_job

SPANS = ("wave_schedule.pack",)


def read(ctx):
    return ms_per_job(ctx, SPANS)

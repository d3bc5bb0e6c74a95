"""Host wave-schedule casts per job, in ms: the program's
``wave_schedule.prepare`` (the int64 copies of the stream, the valid
mask and the scheduled positions, before the ``schedule`` stage) and
``wave_schedule.emit`` (the int32 arrays of the schedule, after the
``pack`` stage) spans. None where the program records neither."""
from perfbench.spans import ms_per_job

SPANS = ("wave_schedule.prepare", "wave_schedule.emit")


def read(ctx):
    return ms_per_job(ctx, SPANS)

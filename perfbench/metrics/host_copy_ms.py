"""Copies between host and device on the job path per job, in ms: the
program's ``copy.d2h`` (stream, slot results and assignments to the
host) and ``copy.h2d`` (slot arrays, carried state and assignments to
the device, each timed to its ``block_until_ready``) spans. None where
the program records no such span."""
from perfbench.spans import ms_per_job

SPANS = ("copy.d2h", "copy.h2d")


def read(ctx):
    return ms_per_job(ctx, SPANS)

"""The matching kernel's share of its roofline, in %: the least time the
chip needs for a job's HBM bytes (``perfbench.roofline.problem_bytes``
over the device's HBM bandwidth) over the kernel's device time per job.
Memory is the bound that applies: the kernel's bitwise work has no
published peak."""
from perfbench import roofline
from perfbench.metrics.kernel_ms import KERNEL


def read(ctx):
    s = ctx.trace.seconds_of(KERNEL)
    if not s or not ctx.jobs:
        return None
    wl = ctx.workload
    least = roofline.problem_bytes(wl.m, wl.n, wl.L) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (s / ctx.jobs)

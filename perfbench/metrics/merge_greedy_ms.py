"""The Part 2 merge's greedy pass per job, in ms: the program's
``merge.greedy`` spans (the loop over the recorded edges in merge
order, inside ``merge.host``). None where the program records none."""
from perfbench.spans import ms_per_job

SPANS = ("merge.greedy",)


def read(ctx):
    return ms_per_job(ctx, SPANS)

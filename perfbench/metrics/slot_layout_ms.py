"""Host slot layout per job, in ms: the program's ``layout.block_align``
(the block-aligned re-padding of the mega path), ``layout.gather`` (the
slot stream) and ``layout.scatter`` (slots back to stream positions)
spans. The ``layout`` stage less its ``copy.d2h`` children. None where
the program records no such span."""
from perfbench.spans import ms_per_job

SPANS = ("layout.block_align", "layout.gather", "layout.scatter")


def read(ctx):
    return ms_per_job(ctx, SPANS)

"""Device time of one dependent kernel trip, in us: the device time of
the pallas_call named ``substream_match`` in the window (as
``kernel_ms`` reads it) over the trips of the window's engine calls,
the program's exact ``kernel.trips`` counter (mega tiles, wave
segments, or one per edge). None where the program counts no trips."""
from perfbench.metrics.kernel_ms import KERNEL


def read(ctx):
    trips = sum(call.counters.get("kernel.trips", 0) for call in ctx.telemetry.match_calls)
    s = ctx.trace.seconds_of(KERNEL)
    if not trips or s is None:
        return None
    return s / trips * 1e6

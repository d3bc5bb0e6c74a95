"""Program entries a traffic mix can drive, one module each.

An entry module has ``ORDER`` (``"stream"`` or ``"blocked"``: the edge
order in which the program's Part 1 processes the stream, which the
reference follows) and ``job(wl, params, ctx) -> reference.Answer``: one
whole job, from the stream as host arrays (``wl.src``, ``wl.dst``,
``wl.weight``) to the merged matching on the host. ``params`` is the
traffic file; ``ctx`` carries the telemetry session (``ctx.telemetry``),
the benchmark's own trace annotations (``ctx.mark(name)``) and the list
that collects epoch latencies (``ctx.epoch_seconds``). Part 1 arrays in
the answer may stay on the device: the harness copies them to the host
after the job's clock has stopped.
"""

"""Share (%) of the encode passes in which the device ran no op: one minus
the union of the device's op intervals over the passes' time, from the
trace. Moves encode_MBps."""

from benchmarks.chip import trace_reduce


def read(ctx):
    return trace_reduce.idle_percent(
        ctx.trace, ctx.trace.spans(ctx.windows["encode"]))

"""Blocking device-to-host reads per encode pass: the program's
``repro.host_read`` spans that start inside the encode passes, over the
number of those passes, from the trace. Moves encode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.per_pass(ctx, "encode", spans.HOST_READ)

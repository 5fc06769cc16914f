"""Blocking device-to-host reads per decode pass: the program's
``repro.host_read`` spans that start inside the decode passes, over the
number of those passes, from the trace. Moves decode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.per_pass(ctx, "decode", spans.HOST_READ)

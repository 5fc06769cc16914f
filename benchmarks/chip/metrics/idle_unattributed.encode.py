"""Share (%) of the device's idle time in the encode passes (pass time
minus the union of the ops of the device that ran the most) that no
``repro.*`` span of the program covers, from the trace: what the
program's spans leave unnamed. Moves encode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.idle_unattributed(ctx, "encode")

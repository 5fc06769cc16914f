"""Share (%) of the decode passes' time inside the program's
``repro.forward`` spans: the VAE's networks and the leaf codec built
on them, run eagerly between the coder programs, from the trace. Moves
decode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.share(ctx, "decode", spans.FORWARD)

"""Share (%) of the encode passes' time inside the program's
``repro.forward`` spans: the VAE's networks and the leaf codec built
on them, run eagerly between the coder programs, from the trace. Moves
encode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.share(ctx, "encode", spans.FORWARD)

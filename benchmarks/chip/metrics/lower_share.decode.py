"""Share (%) of the decode passes' time inside the program's
``repro.lower`` spans: codecs/compile's lowering of a Repeat on every
chain step (probes, tables or grid parameters, their validation and
host reads), from the trace. Moves decode_MBps."""

from benchmarks.chip import spans


def read(ctx):
    return spans.share(ctx, "decode", spans.LOWER)

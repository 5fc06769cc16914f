"""The coder programs' share (%) of their roofline in the encode passes:
the least time for the bytes the coding must move (counts.py, bound by
HBM bandwidth) over the device time of the programs whose names match
PATTERNS. Moves encode_MBps."""

from benchmarks.chip import counts, trace_reduce

#: The coder programs of codecs/compile.py (push_many, pop_many_dyn,
#: pop_many_grid, _push_grid_body, _push_table_body) and the fused
#: fixed-point push_body / pop_body, by their XLA module names.
PATTERNS = (r"push", r"pop")


def read(ctx):
    if not ctx.peaks:          # no chip, no peak
        return None
    seconds = trace_reduce.device_seconds(
        trace_reduce.matching(ctx.trace.modules, PATTERNS),
        ctx.trace.spans(ctx.windows["encode"]))
    if seconds <= 0:
        return None
    moved = ctx.images["encode"] * counts.coder_bytes_per_image(
        ctx.config, "encode", ctx.wire_bytes_per_image)
    return counts.roofline(moved, 0.0, seconds, ctx.peaks)["percent"]

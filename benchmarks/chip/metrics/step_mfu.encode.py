"""Model FLOPs of the encode passes (both networks once per image,
counts.py) per second of pass time, as a share (%) of the chip's bf16
peak. The same count whatever runs the forward. Moves encode_MBps."""

from benchmarks.chip import counts


def read(ctx):
    if not ctx.peaks:          # no chip, no peak
        return None
    seconds = ctx.pass_seconds["encode"]
    if seconds <= 0:
        return None
    flops = ctx.images["encode"] * counts.vae_flops_per_image(ctx.config)
    return 100.0 * flops / seconds / ctx.peaks["bf16_flops"]

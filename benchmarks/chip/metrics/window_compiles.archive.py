"""Backend compiles inside the measured window of an archive cell
(jax.monitoring events); 0 is expected. Moves encode_MBps."""


def read(ctx):
    return ctx.window_compiles.get("archive")

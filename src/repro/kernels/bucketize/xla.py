"""Pure-XLA twin of the fused posterior-decode bucketize kernel.

Same contract as ``kernel.bucketize`` - (slot, mu, sigma) per lane plus
the shared edge table -> (idx, start, freq) - but the bisection runs as
straight-line XLA over the caller's lane count: no LANE_TILE padding,
no Pallas interpreter. The CDF chain is expression-identical to
``kernels.ans.xla._grid_starts_fn`` (which builds the Pallas path's
table) and ``core.discretize``, so the bits match on every backend.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.scipy.special import ndtr


def bucketize(slot: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
              edges: jnp.ndarray, lat_bits: int, precision: int):
    """uint32[lanes], f32[lanes], f32[lanes], f32[K+1] ->
    (idx i32, start u32, freq u32) - any lane count."""
    k = 1 << lat_bits
    scale = float((1 << precision) - k)

    def f(i):
        z = edges[i]
        c = ndtr((z - mu) * (1.0 / sigma))   # canonical form, see core
        c = jnp.where(i <= 0, 0.0, c)
        c = jnp.where(i >= k, 1.0, c)
        return jnp.floor(c * scale).astype(jnp.uint32) \
            + i.astype(jnp.uint32)

    lo = jnp.zeros_like(slot, jnp.int32)
    hi = jnp.full_like(lo, k)
    for _ in range(lat_bits + 1):            # static-count bisection
        mid = (lo + hi + 1) // 2
        up = f(mid) <= slot
        lo = jnp.where(up, mid, lo)
        hi = jnp.where(up, hi, mid)
    start = f(lo)
    return lo, start, f(lo + 1) - start

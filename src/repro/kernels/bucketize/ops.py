"""Dispatched wrapper for the fused posterior-decode bucketize op.

Backend selection follows ``kernels.dispatch`` (XLA twin on CPU,
compiled Pallas on TPU, the interpreter only by name); the Pallas
paths pad lanes to the decision's tile width, the XLA path runs the
caller's lane count as-is.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.bucketize import kernel as K
from repro.kernels.bucketize import xla as X


def bucketize(slot, mu, sigma, lat_bits, precision,
              backend: dispatch.BackendLike = None):
    """uint32[lanes], f32[lanes], f32[lanes] -> (idx i32, start u32,
    freq u32): ``idx = max{i : F(i) <= slot}`` under the pointwise
    fixed-point posterior CDF (see kernel.py). Bit-exact on every
    backend."""
    lanes = slot.shape[0]
    d = dispatch.resolve("bucketize", lanes=lanes, backend=backend)
    mu, sigma = mu.astype(jnp.float32), sigma.astype(jnp.float32)
    if d.backend == "xla":
        return X.bucketize(slot, mu, sigma, K.edge_table(lat_bits),
                           lat_bits, precision)
    pad = (-lanes) % d.lane_tile
    if pad:
        slot = jnp.pad(slot, (0, pad))
        mu = jnp.pad(mu, (0, pad))
        sigma = jnp.pad(sigma, (0, pad), constant_values=1.0)
    idx, start, freq = K.bucketize(slot, mu, sigma, lat_bits, precision,
                                   interpret=(d.backend == "interpret"),
                                   lane_tile=d.lane_tile)
    return idx[:lanes], start[:lanes], freq[:lanes]

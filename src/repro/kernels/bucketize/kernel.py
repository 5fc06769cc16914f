"""Pallas TPU kernel: fused max-entropy Gaussian posterior decode.

Given per-lane (slot, mu, sigma) and the prior's bucket-edge table
``z[i] = ndtri(i / K)``, finds ``idx = max{i : F(i) <= slot}`` for the
pointwise fixed-point posterior CDF ``F(i) = floor(ndtr((z[i]-mu)/sigma)
* (2^prec - K)) + i`` and returns (idx, start, freq) - the per-latent-dim
hot loop of BB-ANS decode.

XLA evaluates ``F`` over the whole grid (``kernels.ans.kernel.
grid_cdf_table``: Mosaic lowers no ``erf``); the kernel searches it by
compare-and-count in int32, so its answer is the XLA twin's bisection
bit for bit (ref.py / core.discretize).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ans import kernel as ans_kernel

LANE_TILE = 128


def edge_table(lat_bits: int) -> jnp.ndarray:
    """z[i] = Phi^-1(i/K) for i = 0..K - the shared concrete table of
    ``core.discretize.edge_table`` (one source of truth: every coding
    path gathers the same bits, whatever the surrounding compilation
    context)."""
    from repro.core import discretize
    return discretize.edge_table(lat_bits)


def _bucketize_kernel(slot_ref, table_ref, idx_ref, start_ref, freq_ref, *,
                      precision: int):
    idx, start, freq = ans_kernel.table_search(
        table_ref[...], slot_ref[...], 1 << precision)
    idx_ref[...] = idx.astype(jnp.int32)
    start_ref[...] = start
    freq_ref[...] = freq


def bucketize(slot: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
              lat_bits: int, precision: int, interpret: bool = True,
              lane_tile: int = LANE_TILE):
    """uint32[lanes], f32[lanes], f32[lanes] -> (idx i32, start u32,
    freq u32). lanes must be a multiple of ``lane_tile`` (ops.py pads)."""
    lanes = slot.shape[0]
    if lanes % lane_tile != 0:
        raise ValueError(
            f"kernels.bucketize: lanes ({lanes}) must be a multiple of "
            f"lane_tile ({lane_tile}); ops.py pads before calling")
    table = ans_kernel.grid_cdf_table(
        mu[None], sigma[None], edge_table(lat_bits), "gaussian", lat_bits,
        precision)[0]
    kernel = functools.partial(_bucketize_kernel, precision=precision)
    spec = pl.BlockSpec((1, lane_tile), lambda i: (0, i))
    idx, start, freq = pl.pallas_call(
        kernel,
        grid=(lanes // lane_tile,),
        in_specs=[spec, pl.BlockSpec((table.shape[0], lane_tile),
                                     lambda i: (0, i))],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, lanes), jnp.int32),
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
        ],
        interpret=interpret,
    )(slot.reshape(1, lanes), table)
    return idx[0], start[0], freq[0]

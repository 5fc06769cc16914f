"""Pure-XLA jitted twins of the Pallas coder kernels (the CPU fast path).

Each entry point mirrors the matching ``kernel.py`` wrapper - same
arguments, same outputs, bit-identical results - but lowers the coder
loop straight through XLA instead of ``pl.pallas_call``:

  * no lane-tile constraint: the caller's lane count runs as-is (the
    Pallas paths pad to a ``lane_tile`` multiple, which on a 4-lane
    codec-compile workload does 32x the useful work);
  * the whole lane axis is one vector per step instead of a grid of
    tiles, so there is no interpreter masking/copy overhead when the
    platform has no Mosaic lowering (CPU);
  * an ``unroll`` knob forwards to ``lax.fori_loop`` - the lane-tiling
    autotuner (``kernels.tuning``) measures candidate unroll factors
    per (op, platform, shape) and persists the winner.

Bit-exactness: the integer renorm arithmetic matches ``kernel.py``
step for step (exact in any fusion context); the grid CDF chain is the
canonical reciprocal-multiply form shared with ``core.discretize``,
stable under fusion by the PR-4 determinism contract, and is also
what builds the Pallas grid pop's table. ``tests/test_dispatch.py`` pins every backend
to the ``ref.py`` oracles and to the committed golden wires.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def push_emit(head: jnp.ndarray, starts: jnp.ndarray, freqs: jnp.ndarray,
              precision: int, unroll: int = 1
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """XLA twin of ``kernel.push_emit``: any lane count, no padding."""
    steps, lanes = starts.shape

    def body(t, carry):
        head, chunks, need = carry
        start = starts[t]
        freq = freqs[t]
        x_max = freq << (32 - precision)
        n = head >= x_max
        chunk = jnp.where(n, head & jnp.uint32(0xFFFF), jnp.uint32(0))
        chunks = chunks.at[t].set(chunk)
        need = need.at[t].set(n.astype(jnp.uint32))
        head = jnp.where(n, head >> 16, head)
        return (((head // freq) << precision) + (head % freq) + start,
                chunks, need)

    zeros = jnp.zeros((steps, lanes), jnp.uint32)
    return jax.lax.fori_loop(0, steps, body, (head, zeros, zeros),
                             unroll=unroll)


def pop_slots(head: jnp.ndarray, precision: int) -> jnp.ndarray:
    """XLA twin of ``kernel.pop_slots``: slot = head mod 2^precision."""
    return head & jnp.uint32((1 << precision) - 1)


def pop_table_emit(head: jnp.ndarray, table: jnp.ndarray,
                   feed: jnp.ndarray, precision: int, unroll: int = 1):
    """XLA twin of ``kernel.pop_table_emit`` (static per-lane table)."""
    steps = feed.shape[0]
    total = jnp.uint32(1 << precision)
    mask = jnp.uint32((1 << precision) - 1)
    table = table.astype(jnp.uint32)

    def body(t, carry):
        head, r, syms = carry
        slot = head & mask
        le = table <= slot[:, None]
        syms = syms.at[t].set(jnp.sum(le, axis=1).astype(jnp.uint32) - 1)
        start = jnp.max(jnp.where(le, table, jnp.uint32(0)), axis=1)
        nxt = jnp.min(jnp.where(le, total, table), axis=1)
        head = (nxt - start) * (head >> precision) + slot - start
        need = head < jnp.uint32(1 << 16)
        chunk = jnp.take_along_axis(feed, r[None, :], axis=0)[0]
        head = jnp.where(need, (head << 16) | chunk, head)
        return head, r + need.astype(jnp.int32), syms

    reads0 = jnp.zeros(head.shape, jnp.int32)
    syms0 = jnp.zeros(feed.shape, jnp.uint32)
    head, reads, syms = jax.lax.fori_loop(
        0, steps, body, (head, reads0, syms0), unroll=unroll)
    return head, syms, reads.astype(jnp.uint32)


def pop_dyntable_emit(head: jnp.ndarray, tables: jnp.ndarray,
                      feed: jnp.ndarray, precision: int, unroll: int = 1):
    """XLA twin of ``kernel.pop_dyntable_emit`` (per-step tables)."""
    steps = feed.shape[0]
    total = jnp.uint32(1 << precision)
    mask = jnp.uint32((1 << precision) - 1)
    tables = tables.astype(jnp.uint32)

    def body(t, carry):
        head, r, syms = carry
        slot = head & mask
        table = tables[t]                        # uint32[lanes, A+1]
        le = table <= slot[:, None]
        syms = syms.at[t].set(jnp.sum(le, axis=1).astype(jnp.uint32) - 1)
        start = jnp.max(jnp.where(le, table, jnp.uint32(0)), axis=1)
        nxt = jnp.min(jnp.where(le, total, table), axis=1)
        head = (nxt - start) * (head >> precision) + slot - start
        need = head < jnp.uint32(1 << 16)
        chunk = jnp.take_along_axis(feed, r[None, :], axis=0)[0]
        head = jnp.where(need, (head << 16) | chunk, head)
        return head, r + need.astype(jnp.int32), syms

    reads0 = jnp.zeros(head.shape, jnp.int32)
    syms0 = jnp.zeros(feed.shape, jnp.uint32)
    head, reads, syms = jax.lax.fori_loop(
        0, steps, body, (head, reads0, syms0), unroll=unroll)
    return head, syms, reads.astype(jnp.uint32)


def _grid_starts_fn(mu_t, sigma_t, edges, kind: str, lat_bits: int,
                    precision: int):
    """The canonical grid CDF chain for one step's (mu, sigma) row -
    expression-identical to ``core.discretize``; ``kernel.
    grid_cdf_table`` evaluates it over the whole grid for the Pallas
    path, so every backend gets one set of bits."""
    from jax.scipy.special import ndtr

    k = 1 << lat_bits
    scale = float((1 << precision) - k)

    def f(i):
        z = edges[i]
        if kind == "gaussian":
            c = ndtr((z - mu_t) * (1.0 / sigma_t))
        else:  # logistic: sigma carries the scale parameter
            c = jax.nn.sigmoid((z - mu_t) * (1.0 / sigma_t))
            c = jnp.clip(c, 0.0, 1.0)
        c = jnp.where(i <= 0, 0.0, c)
        c = jnp.where(i >= k, 1.0, c)
        return jnp.floor(c * scale).astype(jnp.uint32) \
            + i.astype(jnp.uint32)

    return f


def pop_grid_emit(head: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
                  feed: jnp.ndarray, edges: jnp.ndarray, kind: str,
                  lat_bits: int, precision: int, unroll: int = 1):
    """XLA twin of ``kernel.pop_grid_emit`` (fused bucketize+pop).

    The ``lat_bits + 1``-step CDF bisection has a static trip count, so
    it unrolls at trace time (fewer tiny while-loop dispatches on CPU);
    the sequential pop chain stays a ``fori_loop`` with the tuned
    ``unroll``.
    """
    if kind not in ("gaussian", "logistic", "uniform"):
        raise ValueError(
            f"kernels.ans.xla: unknown grid kind {kind!r} (expected "
            "'gaussian', 'logistic', or 'uniform')")
    steps = feed.shape[0]
    k = 1 << lat_bits
    shift = precision - lat_bits
    mask = jnp.uint32((1 << precision) - 1)

    def body(t, carry):
        head, r, idxs = carry
        slot = head & mask
        if kind == "uniform":
            idx = (slot >> shift).astype(jnp.int32)
            start = idx.astype(jnp.uint32) << shift
            freq = jnp.full_like(start, jnp.uint32(1 << shift))
        else:
            f = _grid_starts_fn(mu[t], sigma[t], edges, kind, lat_bits,
                                precision)
            lo = jnp.zeros(slot.shape, jnp.int32)
            hi = jnp.full(slot.shape, k, jnp.int32)
            for _ in range(lat_bits + 1):     # static-count bisection
                mid = (lo + hi + 1) // 2
                up = f(mid) <= slot
                lo = jnp.where(up, mid, lo)
                hi = jnp.where(up, hi, mid)
            idx = lo
            start = f(idx)
            freq = f(idx + 1) - start
        idxs = idxs.at[t].set(idx.astype(jnp.uint32))
        head = freq * (head >> precision) + slot - start
        need = head < jnp.uint32(1 << 16)
        chunk = jnp.take_along_axis(feed, r[None, :], axis=0)[0]
        head = jnp.where(need, (head << 16) | chunk, head)
        return head, r + need.astype(jnp.int32), idxs

    reads0 = jnp.zeros(head.shape, jnp.int32)
    idxs0 = jnp.zeros(feed.shape, jnp.uint32)
    head, reads, idxs = jax.lax.fori_loop(
        0, steps, body, (head, reads0, idxs0), unroll=unroll)
    return head, idxs, reads.astype(jnp.uint32)

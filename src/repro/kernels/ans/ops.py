"""Dispatched ANSStack coder ops: one public surface, three backends.

``push_many`` is the production batch-encode path: the ALU-bound coder
loop runs in whichever backend ``kernels.dispatch`` resolves - the
pure-XLA twin (``xla.py``, the CPU fast path: no lane padding, tunable
unroll), the compiled Pallas kernel (``kernel.py``, TPU only), or the
Pallas interpreter when a test names it - and the irregular
per-lane stack append becomes one vectorized cumsum + scatter.
``pop_many`` is its decode twin: the table search and state updates run
in the selected backend against a pre-gathered chunk feed (each pop
reads at most one chunk, in stack order, so the feed is a dense
[steps, lanes] slice), and the per-lane pointer/underflow bookkeeping
happens outside. All backends are bit-exact equivalents of the
sequential ``repro.core.ans`` calls, validated against the ``ref.py``
oracle and each other (tests/test_dispatch.py); ``repro.stream`` and
``codecs.compile`` use them as the block coder's fast path.

``backend=`` accepts None (resolve via env / context / tuning cache /
platform heuristic), a backend name, or a full ``dispatch.Decision``
(hashable, so compiled programs pass it through ``jax.jit`` statically).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import ans
from repro.kernels import dispatch
from repro.kernels.ans import kernel as K
from repro.kernels.ans import xla as X


def push_many(stack: ans.ANSStack, starts: jnp.ndarray, freqs: jnp.ndarray,
              precision: int = ans.DEFAULT_PRECISION,
              backend: dispatch.BackendLike = None) -> ans.ANSStack:
    """Push ``steps`` symbols per lane. starts/freqs uint32[steps, lanes].

    Bit-exact equivalent of ``steps`` sequential ``ans.push`` calls,
    whatever backend resolves.
    """
    steps, lanes = starts.shape
    d = dispatch.resolve("push_many", lanes=lanes, backend=backend)
    if d.backend == "xla":
        new_head, chunks, need = X.push_emit(stack.head, starts, freqs,
                                             precision, unroll=d.unroll)
    else:
        head = stack.head
        pad = (-lanes) % d.lane_tile
        if pad:
            head = jnp.pad(head, (0, pad), constant_values=1 << 16)
            starts = jnp.pad(starts, ((0, 0), (0, pad)))
            freqs = jnp.pad(freqs, ((0, 0), (0, pad)), constant_values=1)
        new_head, chunks, need = K.push_emit(
            head, starts, freqs, precision,
            interpret=(d.backend == "interpret"), lane_tile=d.lane_tile)
        new_head = new_head[:lanes]
        chunks = chunks[:, :lanes]
        need = need[:, :lanes]
    # Compaction: chunk emitted at step t lands at ptr + (#emits before t).
    before = jnp.cumsum(need, axis=0) - need
    pos = stack.ptr[None, :] + before
    cols = jnp.where(need.astype(bool), pos, stack.capacity)  # drop if not
    rows = jnp.broadcast_to(jnp.arange(lanes)[None, :], cols.shape)
    buf = stack.buf.at[rows, cols].set(chunks.astype(jnp.uint16),
                                       mode="drop")
    ptr = stack.ptr + jnp.sum(need, axis=0).astype(jnp.int32)
    over = jnp.sum(need.astype(bool) & (pos >= stack.capacity),
                   axis=0).astype(jnp.int32)
    return stack._replace(head=new_head, buf=buf, ptr=ptr,
                          overflows=stack.overflows + over)


def push_many_table(stack: ans.ANSStack, starts_table: jnp.ndarray,
                    symbols: jnp.ndarray,
                    precision: int = ans.DEFAULT_PRECISION,
                    backend: dispatch.BackendLike = None) -> ans.ANSStack:
    """Push ``steps`` symbols per lane from a static per-lane table.

    ``starts_table``: uint32[lanes, A+1] cumulative starts (as in
    ``ans.push_with_table``); ``symbols``: int[steps, lanes]. Bit-exact
    equivalent of ``steps`` sequential ``ans.push_with_table`` calls.
    """
    sym = symbols.astype(jnp.int32)
    rows = jnp.arange(stack.lanes)[None, :]
    starts = starts_table[rows, sym]
    freqs = starts_table[rows, sym + 1] - starts
    if backend is None:
        backend = dispatch.resolve(
            "push_many_table", lanes=stack.lanes,
            table_size=starts_table.shape[-1] - 1)
    return push_many(stack, starts.astype(jnp.uint32),
                     freqs.astype(jnp.uint32), precision, backend)


def _chunk_feed(stack: ans.ANSStack, steps: int) -> jnp.ndarray:
    """Pre-gather the renormalization chunk feed for a ``steps``-pop.

    ``feed[r, l]`` is the ``r``-th chunk lane ``l``'s stack would serve:
    ``buf[l, ptr-1-r]`` clamped at the bottom (the core re-serves the
    bottom chunk on underflow - replicated here for bit-exactness).
    """
    lanes = stack.lanes
    if not stack.capacity:   # chunk-less stack: every read serves 0
        return jnp.zeros((steps, lanes), jnp.uint32)
    t = jnp.arange(steps)
    cols = jnp.clip(stack.ptr[None, :] - 1 - t[:, None], 0,
                    stack.capacity - 1)
    return stack.buf[jnp.arange(lanes)[None, :], cols].astype(jnp.uint32)


def _finish_pop(stack: ans.ANSStack, new_head: jnp.ndarray,
                syms: jnp.ndarray, reads: jnp.ndarray
                ) -> Tuple[ans.ANSStack, jnp.ndarray]:
    """Apply the kernel's (head, reads) to the stack bookkeeping."""
    lanes = stack.lanes
    new_head = new_head[:lanes]
    syms = syms[:, :lanes].astype(jnp.int32)
    reads = reads[:lanes].astype(jnp.int32)
    under = jnp.maximum(reads - stack.ptr, 0)
    ptr = jnp.maximum(stack.ptr - reads, 0)
    return stack._replace(head=new_head, ptr=ptr,
                          underflows=stack.underflows + under), syms


def pop_many(stack: ans.ANSStack, starts_table: jnp.ndarray, steps: int,
             precision: int = ans.DEFAULT_PRECISION,
             backend: dispatch.BackendLike = None
             ) -> Tuple[ans.ANSStack, jnp.ndarray]:
    """Pop ``steps`` symbols per lane from a static per-lane table.

    Bit-exact equivalent of ``steps`` sequential ``ans.pop_with_table``
    calls, including the underflow accounting (reads past the stack
    bottom re-serve the bottom chunk, exactly as ``ans.pop_update``
    does). Returns ``(stack, symbols int32[steps, lanes])`` with symbols
    in pop order.
    """
    lanes = stack.lanes
    d = dispatch.resolve("pop_many", lanes=lanes,
                         table_size=starts_table.shape[-1] - 1,
                         backend=backend)
    feed = _chunk_feed(stack, steps)
    head, table = stack.head, starts_table.astype(jnp.uint32)
    if d.backend == "xla":
        new_head, syms, reads = X.pop_table_emit(head, table, feed,
                                                 precision,
                                                 unroll=d.unroll)
    else:
        pad = (-lanes) % d.lane_tile
        if pad:
            head = jnp.pad(head, (0, pad), constant_values=1 << 16)
            table = jnp.pad(table, ((0, pad), (0, 0)))
            feed = jnp.pad(feed, ((0, 0), (0, pad)))
        new_head, syms, reads = K.pop_table_emit(
            head, table, feed, precision,
            interpret=(d.backend == "interpret"), lane_tile=d.lane_tile)
    return _finish_pop(stack, new_head, syms, reads)


def pop_many_dyn(stack: ans.ANSStack, tables: jnp.ndarray,
                 precision: int = ans.DEFAULT_PRECISION,
                 backend: dispatch.BackendLike = None
                 ) -> Tuple[ans.ANSStack, jnp.ndarray]:
    """Pop ``steps`` symbols per lane from *per-step* dynamic tables.

    ``tables``: uint32[steps, lanes, A+1] cumulative starts, one table
    per step per lane (the decode twin of the dynamic ``push_many``).
    Bit-exact equivalent of ``steps`` sequential ``ans.pop_with_table``
    calls against ``tables[t]``. Returns ``(stack, symbols int32[steps,
    lanes])`` in pop order.
    """
    steps, lanes = tables.shape[0], stack.lanes
    d = dispatch.resolve("pop_many_dyn", lanes=lanes,
                         table_size=tables.shape[-1] - 1, backend=backend)
    feed = _chunk_feed(stack, steps)
    head, tables = stack.head, tables.astype(jnp.uint32)
    if d.backend == "xla":
        new_head, syms, reads = X.pop_dyntable_emit(head, tables, feed,
                                                    precision,
                                                    unroll=d.unroll)
    else:
        pad = (-lanes) % d.lane_tile
        if pad:
            head = jnp.pad(head, (0, pad), constant_values=1 << 16)
            tables = jnp.pad(tables, ((0, 0), (0, pad), (0, 0)))
            feed = jnp.pad(feed, ((0, 0), (0, pad)))
        new_head, syms, reads = K.pop_dyntable_emit(
            head, tables, feed, precision,
            interpret=(d.backend == "interpret"), lane_tile=d.lane_tile)
    return _finish_pop(stack, new_head, syms, reads)


def pop_many_grid(stack: ans.ANSStack, kind: str, mu: jnp.ndarray,
                  sigma: jnp.ndarray, steps: int, lat_bits: int,
                  precision: int = ans.DEFAULT_PRECISION,
                  backend: dispatch.BackendLike = None
                  ) -> Tuple[ans.ANSStack, jnp.ndarray]:
    """Fused bucketize+pop over the max-entropy N(0,1) bucket grid.

    Decodes ``steps`` bucket indices per lane under per-step
    distributions on the shared grid: ``kind="gaussian"`` is bit-exact
    vs sequential ``discretize.pop_posterior(mu[t], sigma[t])``,
    ``"logistic"`` vs ``codecs.DiscretizedLogistic(mu[t], sigma[t])``
    pops (``sigma`` carries the scale), ``"uniform"`` vs
    ``discretize.pop_prior`` (mu/sigma ignored; pass zeros). The CDF
    bisection of ``kernels/bucketize`` runs inside the pop renorm chain
    - one program for the whole [steps, lanes] grid.
    """
    from repro.kernels.bucketize import kernel as BK

    lanes = stack.lanes
    d = dispatch.resolve("pop_many_grid", lanes=lanes, backend=backend)
    feed = _chunk_feed(stack, steps)
    head = stack.head
    if kind == "uniform":
        mu = jnp.zeros((steps, lanes), jnp.float32)
        sigma = jnp.ones((steps, lanes), jnp.float32)
        edges = jnp.zeros((2,), jnp.float32)
    else:
        mu = mu.astype(jnp.float32)
        sigma = sigma.astype(jnp.float32)
        edges = BK.edge_table(lat_bits)
    if d.backend == "xla":
        new_head, idx, reads = X.pop_grid_emit(head, mu, sigma, feed,
                                               edges, kind, lat_bits,
                                               precision, unroll=d.unroll)
    else:
        pad = (-lanes) % d.lane_tile
        if pad:
            head = jnp.pad(head, (0, pad), constant_values=1 << 16)
            mu = jnp.pad(mu, ((0, 0), (0, pad)))
            sigma = jnp.pad(sigma, ((0, 0), (0, pad)),
                            constant_values=1.0)
            feed = jnp.pad(feed, ((0, 0), (0, pad)))
        new_head, idx, reads = K.pop_grid_emit(
            head, mu, sigma, feed, edges, kind, lat_bits, precision,
            interpret=(d.backend == "interpret"), lane_tile=d.lane_tile)
    return _finish_pop(stack, new_head, idx, reads)

"""Pallas TPU kernels: the lane-vectorized rANS coder loops.

TPU mapping: lanes lie on the 128-wide minor axis of every block. The
grid is (lane tiles, step blocks): lane tiles are independent
("parallel"); step blocks run in order ("arbitrary"), with the carried
coder state (heads, per-lane read counters) resident in the output
blocks across them. ``_step_block`` sizes the step blocks so that a
long ``Repeat`` never outgrows VMEM, and ``_compiler_params`` states
the VMEM the blocks need. Heads travel as ``[1, lanes]`` rows (a 1-D
block's layout is not one Mosaic accepts above 128 lanes).

Everything inside a kernel is integer arithmetic:

  * the data-dependent "emit" branch of scalar rANS is a masked vector
    op (uint32 state, 16-bit renorm: at most one emission per push);
  * the decode symbol search is compare-and-count over a cumulative
    starts table, in int32 (Mosaic reduces no unsigned integers):
    ``sym = #(F <= slot) - 1``, ``start = max F <= slot``,
    ``next = min F > slot``;
  * the renormalization read takes lane ``l``'s ``r[l]``-th chunk of a
    feed pre-gathered in XLA, by compare-and-select over aligned 8-row
    groups (Mosaic lowers no per-lane vector gather);
  * the Gaussian and logistic grid pops get their fixed-point CDF as a
    table built in XLA from the canonical starts expression
    (``xla._grid_starts_fn``) and run the dynamic-table kernel. Mosaic
    lowers no ``erf``, and keeping float math out of the kernel keeps
    its bits those of the XLA twins by construction.

Stack compaction (a cumsum scatter) stays outside in XLA. Validated
bit-exactly against the pure-jnp oracle (ref.py) under interpret=True
(tests/test_kernels.py, tests/test_dispatch.py); every kernel is also
compiled for a described v5e chip by tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_TILE = 128

# VMEM for one buffer of the step-blocked operands; Pallas double
# buffers them, and the whole-stream feed comes on top.
_STEP_BLOCK_BYTES = 4 << 20
_VMEM_FLOOR_BYTES = 16 << 20


def _check_lanes(lanes: int, lane_tile: int = LANE_TILE) -> None:
    # Explicit raise rather than assert: the invariant must survive
    # python -O (ops.py pads to a lane_tile multiple before calling).
    if lanes % lane_tile != 0:
        raise ValueError(
            f"kernels.ans: lanes ({lanes}) must be a multiple of "
            f"lane_tile ({lane_tile}); ops.py pads before calling")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _step_block(steps: int, row_bytes: int) -> int:
    """Steps per grid block: all of them when they fit the budget, else
    a multiple of 8 (the sublane tile of the ``[steps, lanes]`` blocks).
    ``row_bytes`` is the VMEM one step occupies across the step-blocked
    operands."""
    if steps * row_bytes <= _STEP_BLOCK_BYTES:
        return steps
    return max(8, _STEP_BLOCK_BYTES // row_bytes // 8 * 8)


def _pad_steps(x: jnp.ndarray, padded: int) -> jnp.ndarray:
    if x.shape[0] == padded:
        return x
    widths = [(0, padded - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


def _compiler_params(step_block_bytes: int, resident_bytes: int):
    need = 2 * (step_block_bytes + resident_bytes) + (4 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(_VMEM_FLOOR_BYTES, need))


def _block_len(steps: int, st: int):
    """Valid steps in the current step block (static when ``st``
    divides ``steps``; the padded tail block runs short)."""
    if steps % st == 0:
        return st
    return jnp.minimum(st, steps - pl.program_id(1) * st)


def _push_kernel(head_ref, starts_ref, freqs_ref,
                 out_head_ref, chunks_ref, need_ref, *, precision: int,
                 steps: int, st: int):
    """One (lane tile, step block): push the block's symbols per lane.

    head_ref/out_head_ref: uint32[1, LANE_TILE]; starts/freqs/chunks/
    need: uint32[st, LANE_TILE]. ``out_head_ref`` carries the heads
    across step blocks.
    """
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_head_ref[...] = head_ref[...]

    def body(t, head):
        start = starts_ref[pl.ds(t, 1), :]
        freq = freqs_ref[pl.ds(t, 1), :]
        x_max = freq << (32 - precision)
        need = head >= x_max
        chunks_ref[pl.ds(t, 1), :] = jnp.where(
            need, head & 0xFFFF, 0).astype(jnp.uint32)
        need_ref[pl.ds(t, 1), :] = need.astype(jnp.uint32)
        head = jnp.where(need, head >> 16, head)
        return ((head // freq) << precision) + (head % freq) + start

    out_head_ref[...] = jax.lax.fori_loop(0, _block_len(steps, st), body,
                                          out_head_ref[...])


def push_emit(head: jnp.ndarray, starts: jnp.ndarray, freqs: jnp.ndarray,
              precision: int, interpret: bool = True,
              lane_tile: int = LANE_TILE):
    """head uint32[lanes]; starts/freqs uint32[steps, lanes] ->
    (new_head, chunks uint32[steps, lanes], need uint32[steps, lanes]).

    lanes must be a multiple of ``lane_tile`` (ops.py pads).
    """
    steps, lanes = starts.shape
    _check_lanes(lanes, lane_tile)
    row_bytes = 4 * lane_tile * 4
    st = _step_block(steps, row_bytes)
    padded = _round_up(steps, st)
    kernel = functools.partial(_push_kernel, precision=precision,
                               steps=steps, st=st)
    head_spec = pl.BlockSpec((1, lane_tile), lambda i, j: (0, i))
    row_spec = pl.BlockSpec((st, lane_tile), lambda i, j: (j, i))
    new_head, chunks, need = pl.pallas_call(
        kernel,
        grid=(lanes // lane_tile, padded // st),
        in_specs=[head_spec, row_spec, row_spec],
        out_specs=[head_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((padded, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((padded, lanes), jnp.uint32),
        ],
        compiler_params=_compiler_params(st * row_bytes, 0),
        interpret=interpret,
    )(head.reshape(1, lanes), _pad_steps(starts, padded),
      _pad_steps(freqs, padded))
    return new_head[0], chunks[:steps], need[:steps]


def _peek_kernel(head_ref, slots_out_ref, *, precision: int):
    """Single-step vector peek: the decode slot per lane.

    The honest single-step kernel: one masked AND per lane, no loop. The
    real multi-step decode path is ``_pop_kernel`` below.
    """
    mask = jnp.uint32((1 << precision) - 1)
    slots_out_ref[...] = head_ref[...] & mask


def pop_slots(head: jnp.ndarray, precision: int,
              interpret: bool = True,
              lane_tile: int = LANE_TILE) -> jnp.ndarray:
    """Vector peek: slot = head mod 2^precision per lane."""
    lanes = head.shape[0]
    _check_lanes(lanes, lane_tile)
    kernel = functools.partial(_peek_kernel, precision=precision)
    spec = pl.BlockSpec((1, lane_tile), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(lanes // lane_tile,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
        interpret=interpret,
    )(head.reshape(1, lanes))
    return out[0]


# ---------------------------------------------------------------------------
# decode: one renormalization loop, three symbol searches
# ---------------------------------------------------------------------------

def _feed_chunk(feed_ref, r):
    """``feed[r[l], l]`` per lane, by compare-and-select over the 8-row
    groups that hold the tile's read counters (``r`` int32[1, tile])."""
    tile = r.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, tile), 0)

    def group(g, acc):
        base = pl.multiple_of(g * 8, 8)
        block = feed_ref[pl.ds(base, 8), :].astype(jnp.int32)
        hit = rows + base == r
        return acc + jnp.sum(jnp.where(hit, block, 0), axis=0,
                             keepdims=True)

    acc = jax.lax.fori_loop(jnp.min(r) // 8, jnp.max(r) // 8 + 1, group,
                            jnp.zeros_like(r))
    return acc.astype(jnp.uint32)


def table_search(table, slot, total: int):
    """Compare-and-count over cumulative starts ``table`` int32[A+1,
    tile] for ``slot`` uint32[1, tile] -> (sym, start, freq) uint32."""
    s = slot.astype(jnp.int32)
    le = table <= s
    sym = jnp.sum(le.astype(jnp.int32), axis=0, keepdims=True) - 1
    start = jnp.max(jnp.where(le, table, 0), axis=0, keepdims=True)
    nxt = jnp.min(jnp.where(le, total, table), axis=0, keepdims=True)
    return (sym.astype(jnp.uint32), start.astype(jnp.uint32),
            (nxt - start).astype(jnp.uint32))


def _pop_kernel(*refs, search: str, precision: int, steps: int, st: int,
                shift: int = 0):
    """One (lane tile, step block) of multi-step decoding.

    ``search`` picks how a slot maps to (sym, start, freq): ``"table"``
    (one static int32[A+1, tile] table), ``"dyn"`` (int32[st, A+1,
    tile], a table per step) or ``"uniform"`` (closed form, ``shift =
    precision - bits``). The renormalization read is fed from
    ``feed_ref`` (uint32[steps8, tile]): the chunks each lane's stack
    would serve, in pop order (each pop reads at most one). Heads and
    read counters stay in their output blocks across step blocks.
    """
    if search == "uniform":
        head_ref, feed_ref, out_head_ref, syms_ref, reads_ref = refs
    else:
        (head_ref, feed_ref, table_ref,
         out_head_ref, syms_ref, reads_ref) = refs
    total = 1 << precision
    mask = jnp.uint32(total - 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_head_ref[...] = head_ref[...]
        reads_ref[...] = jnp.zeros(reads_ref.shape, jnp.int32)

    if search == "table":
        table = table_ref[...]

    def body(t, carry):
        head, r = carry
        slot = head & mask
        if search == "uniform":
            sym = slot >> shift
            start = sym << shift
            freq = jnp.full_like(start, jnp.uint32(1 << shift))
        elif search == "table":
            sym, start, freq = table_search(table, slot, total)
        else:
            sym, start, freq = table_search(table_ref[t], slot, total)
        syms_ref[pl.ds(t, 1), :] = sym
        head = freq * (head >> precision) + slot - start
        need = head < jnp.uint32(1 << 16)
        head = jnp.where(need, (head << 16) | _feed_chunk(feed_ref, r),
                         head)
        return head, r + need.astype(jnp.int32)

    head, reads = jax.lax.fori_loop(0, _block_len(steps, st), body,
                                    (out_head_ref[...], reads_ref[...]))
    out_head_ref[...] = head
    reads_ref[...] = reads


def _pop_call(head, feed, table, *, search: str, precision: int,
              interpret: bool, lane_tile: int, shift: int = 0):
    """Shared pallas_call plumbing of the decode kernels.

    ``table``: None (uniform), int32[A+1, lanes] (static) or
    int32[steps, A+1, lanes] (per step). Returns (new_head uint32[lanes],
    syms uint32[steps, lanes], reads uint32[lanes]).
    """
    steps, lanes = feed.shape
    _check_lanes(lanes, lane_tile)
    # Per step: one syms row, plus a table when it changes every step
    # (VMEM pads its A+1 rows to the 8-row sublane tile).
    rows = 1 + (_round_up(table.shape[1], 8) if search == "dyn" else 0)
    row_bytes = rows * lane_tile * 4
    st = _step_block(steps, row_bytes)
    padded = _round_up(steps, st)
    steps8 = _round_up(steps, 8)
    resident = steps8 * lane_tile * 4
    head_spec = pl.BlockSpec((1, lane_tile), lambda i, j: (0, i))
    in_specs = [head_spec,
                pl.BlockSpec((steps8, lane_tile), lambda i, j: (0, i))]
    args = [head.reshape(1, lanes), _pad_steps(feed, steps8)]
    if search == "table":
        a1 = table.shape[0]
        in_specs.append(pl.BlockSpec((a1, lane_tile), lambda i, j: (0, i)))
        args.append(table)
        resident += _round_up(a1, 8) * lane_tile * 4
    elif search == "dyn":
        a1 = table.shape[1]
        in_specs.append(pl.BlockSpec((st, a1, lane_tile),
                                     lambda i, j: (j, 0, i)))
        args.append(_pad_steps(table, padded))
    kernel = functools.partial(_pop_kernel, search=search,
                               precision=precision, steps=steps, st=st,
                               shift=shift)
    new_head, syms, reads = pl.pallas_call(
        kernel,
        grid=(lanes // lane_tile, padded // st),
        in_specs=in_specs,
        out_specs=[head_spec,
                   pl.BlockSpec((st, lane_tile), lambda i, j: (j, i)),
                   head_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((padded, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((1, lanes), jnp.int32),
        ],
        compiler_params=_compiler_params(st * row_bytes, resident),
        interpret=interpret,
    )(*args)
    return new_head[0], syms[:steps], reads[0].astype(jnp.uint32)


def pop_table_emit(head: jnp.ndarray, table: jnp.ndarray,
                   feed: jnp.ndarray, precision: int,
                   interpret: bool = True, lane_tile: int = LANE_TILE):
    """head uint32[lanes]; table uint32[lanes, A+1]; feed uint32[steps,
    lanes] -> (new_head, syms uint32[steps, lanes], reads uint32[lanes]).

    ``feed[r, l]`` must hold the ``r``-th chunk lane ``l``'s stack would
    serve (top first, clamped at the bottom - see ops.pop_many). lanes
    must be a multiple of ``lane_tile`` (ops.py pads).
    """
    return _pop_call(head, feed, table.astype(jnp.int32).T, search="table",
                     precision=precision, interpret=interpret,
                     lane_tile=lane_tile)


def pop_dyntable_emit(head: jnp.ndarray, tables: jnp.ndarray,
                      feed: jnp.ndarray, precision: int,
                      interpret: bool = True, lane_tile: int = LANE_TILE):
    """head uint32[lanes]; tables uint32[steps, lanes, A+1] (one table
    per step per lane - the decode twin of the dynamic ``push_emit``,
    used by the codec compiler for per-position Bernoulli/Categorical/
    BetaBinomial leaves); feed uint32[steps, lanes] -> (new_head, syms
    uint32[steps, lanes], reads uint32[lanes]). lanes must be a multiple
    of ``lane_tile``."""
    return _pop_call(head, feed,
                     jnp.swapaxes(tables.astype(jnp.int32), 1, 2),
                     search="dyn", precision=precision,
                     interpret=interpret, lane_tile=lane_tile)


def grid_cdf_table(mu: jnp.ndarray, sigma: jnp.ndarray, edges: jnp.ndarray,
                   kind: str, lat_bits: int, precision: int) -> jnp.ndarray:
    """The fixed-point grid CDF ``F(i)``, i = 0..K, of every (step, lane)
    as int32[steps, K+1, lanes], from the canonical starts expression
    the XLA twins bisect pointwise."""
    from repro.kernels.ans.xla import _grid_starts_fn

    f = _grid_starts_fn(mu[:, None, :], sigma[:, None, :], edges, kind,
                        lat_bits, precision)
    i = jnp.arange((1 << lat_bits) + 1, dtype=jnp.int32)[None, :, None]
    return f(i).astype(jnp.int32)


def pop_grid_emit(head: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
                  feed: jnp.ndarray, edges: jnp.ndarray, kind: str,
                  lat_bits: int, precision: int, interpret: bool = True,
                  lane_tile: int = LANE_TILE):
    """head uint32[lanes]; mu/sigma float32[steps, lanes]; feed
    uint32[steps, lanes]; edges float32[K+1] -> (new_head, idx
    uint32[steps, lanes], reads uint32[lanes]).

    Decodes max-entropy grid buckets: ``kind="uniform"`` in closed form
    inside the kernel (mu/sigma/edges ignored); ``"gaussian"`` (ndtr)
    and ``"logistic"`` (sigmoid; sigma carries the scale) through
    ``grid_cdf_table`` and the dynamic-table kernel. lanes must be a
    multiple of ``lane_tile`` (ops.py pads).
    """
    if kind not in ("gaussian", "logistic", "uniform"):
        raise ValueError(
            f"kernels.ans: unknown grid kind {kind!r} (expected "
            "'gaussian', 'logistic', or 'uniform')")
    if kind == "uniform":
        return _pop_call(head, feed, None, search="uniform",
                         precision=precision, interpret=interpret,
                         lane_tile=lane_tile, shift=precision - lat_bits)
    table = grid_cdf_table(mu, sigma, edges, kind, lat_bits, precision)
    return _pop_call(head, feed, table, search="dyn", precision=precision,
                     interpret=interpret, lane_tile=lane_tile)

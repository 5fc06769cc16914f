"""Incremental ``StreamEncoder``/``StreamDecoder`` over the BBX2 format.

The encoder accepts arbitrary-length, time-major symbol arrays
(``[n, lanes, ...]`` pytrees, the ``Chained`` layout), buffers them, and
cuts the stream into fixed-size blocks of ``block_symbols`` datapoints.
Each block is coded on a *fresh* ``ANSStack`` and flushed independently
- that is what makes blocks separately decodable and mid-stream resume
possible - but the stack is **not** seeded with fresh randomness:

  * the initial heads of block ``b+1`` are the *final* heads of block
    ``b`` (carried encoder-side only; the decoder recovers them as the
    residue of block ``b+1``'s pops and simply discards them), so the
    per-block head churn telescopes away and the streamed rate tracks
    the one-shot ``codecs.compress`` rate;
  * bits-back codecs still need a per-block clean-bit supply for their
    first posterior pop (the carried head holds at most ~16 bits);
    ``init_chunks`` seeds it deterministically per block and grows
    automatically on underflow, exactly like the one-shot container.

Within a block, datapoints are pushed in *reverse* so the decoder pops
them in natural order - a streaming decoder yields datapoint ``t``
before it has looked at datapoint ``t+1``.

Fast paths: when the per-datapoint codec is a static-table
``Categorical``, whole blocks go through the Pallas-kernel batch coder
(``kernels.ans.ops.push_many_table``/``pop_many``) instead of ``k``
sequential pushes; with ``compile=True`` every block body is lowered by
the codec compiler (``codecs.compile``) into one fused jit program per
block size (dynamic-leaf codecs included - see docs/PERF.md). All paths
are bit-identical (tested), so the wire format does not know which one
produced a block.

``pipeline=True`` double-buffers blocks: block ``b+1``'s fused push is
dispatched against the *lazy* final heads of block ``b`` before block
``b`` is synced, so model compute for the next block overlaps coder
host work (flatten/framing) for the current one. The overflow/underflow
check of a block is deferred to the moment the next block is dispatched
(or to ``flush``); on a retry the optimistic dispatch is discarded and
both blocks are redone from the corrected heads - wire bytes are
asserted identical to the synchronous path (tests/test_stream.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ans
from repro.core.codec import Codec
from repro.core.distributions import Categorical
from repro.codecs.compile import compile as compile_codec
from repro.codecs.compile import register_lowering
from repro.kernels.ans import ops as ans_ops
from repro.stream import format as fmt

BlockCodecFn = Callable[[int], Codec]


@dataclasses.dataclass(frozen=True)
class _PendingBlock:
    """An encoded-but-unsynced block in the ``pipeline=True`` path.

    ``stack`` is the lazy result of the block's push (device work may
    still be in flight); ``bits_before`` is the lazy content-bit count
    of the stack it started from. ``xs``/``k``/``cap``/``chunks`` are
    kept so the block can be redone synchronously if the deferred
    overflow/underflow check fails.
    """

    xs: Any
    k: int
    stack: ans.ANSStack
    bits_before: jnp.ndarray
    cap: int
    chunks: int


@dataclasses.dataclass(frozen=True)
class EncoderSnapshot:
    """Resumable ``StreamEncoder`` state, captured at a block boundary.

    Everything a fresh process needs to *continue the exact byte
    stream*: the carried clean-bit heads, the block counter (per-block
    seeding is ``fold_in(PRNGKey(seed), n_blocks)``, so the counter
    pins the clean-bit supply), the grow-and-retry state
    (``capacity``/``init_chunks``), and the wire byte offset already
    emitted. All fields are plain Python values, so a snapshot JSON-
    serializes into a ``repro.gateway.recovery`` record as-is.
    """

    lanes: int
    block_symbols: int
    precision: int
    seed: Optional[int]
    init_chunks: int
    capacity: Optional[int]
    n_blocks: int
    n_symbols: int
    wire_bytes: int
    net_bits: float
    started: bool
    heads: Optional[Tuple[int, ...]]   # carried per-lane heads, or None


@dataclasses.dataclass(frozen=True)
class BlockChain(Codec):
    """Chain ``inner`` over a leading time axis ``[k, lanes, ...]``.

    Pushes datapoints in reverse so pops stream in natural order (the
    streaming mirror of ``codecs.Chained``). Python-driven, so inner
    codecs may drive jit-compiled network steps (the ``lm_codec``
    determinism contract).

    Example::

        block = BlockChain(codecs.Uniform(8), k=4)
        stack = block.push(stack, xs)          # xs int[4, lanes]
        stack, xs2 = block.pop(stack)
    """

    inner: Codec
    k: int

    def push(self, stack: ans.ANSStack, xs: Any) -> ans.ANSStack:
        for t in reversed(range(self.k)):
            x_t = jax.tree_util.tree_map(lambda a: a[t], xs)
            stack = self.inner.push(stack, x_t)
        return stack

    def pop(self, stack: ans.ANSStack) -> Tuple[ans.ANSStack, Any]:
        outs = []
        for _ in range(self.k):
            stack, x = self.inner.pop(stack)
            outs.append(x)
        return stack, jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, axis=0), *outs)


@dataclasses.dataclass(frozen=True)
class KernelTableBlock(Codec):
    """Kernel fast path for static-table categorical block coding.

    Symbols are int[k, lanes] (time-major); push/pop are bit-identical
    to ``BlockChain(Categorical(...), k)`` but run the whole block
    through one ``push_many_table``/``pop_many`` kernel call, on
    whichever backend ``kernels.dispatch`` resolves (``backend=None``
    here means auto: env var / ``use_backend`` context / tuning cache /
    platform heuristic - set it to pin one).

    Example::

        cat = Categorical(logits)
        fast = KernelTableBlock(cat._table(), k)   # same wire bytes as
        stack = fast.push(stack, xs)               # BlockChain(cat, k)
    """

    table: jnp.ndarray   # uint32[lanes, A+1]
    k: int
    precision: int = ans.DEFAULT_PRECISION
    backend: Optional[str] = None

    def push(self, stack: ans.ANSStack, xs: jnp.ndarray) -> ans.ANSStack:
        return ans_ops.push_many_table(stack, self.table, xs[::-1],
                                       self.precision,
                                       backend=self.backend)

    def pop(self, stack: ans.ANSStack) -> Tuple[ans.ANSStack, jnp.ndarray]:
        return ans_ops.pop_many(stack, self.table, self.k, self.precision,
                                backend=self.backend)


# The compiler lowers a BlockChain by lowering its inner codec; block
# structure (reversed pushes, natural pops) is preserved bit-exactly.
register_lowering(BlockChain,
                  lambda c, rec: BlockChain(rec(c.inner), c.k))


def _resolve_block_codec(codec: Optional[Codec],
                         block_codec_fn: Optional[BlockCodecFn],
                         use_kernel: bool,
                         compile: bool = False) -> BlockCodecFn:
    if block_codec_fn is None:
        if codec is None:
            raise ValueError("stream: pass a per-datapoint codec or a "
                             "block_codec_fn")
        if use_kernel and isinstance(codec, Categorical):
            table = codec._table()
            prec = codec.precision
            block_codec_fn = lambda k: KernelTableBlock(table, k, prec)
        else:
            block_codec_fn = lambda k: BlockChain(codec, k)
    if not compile:
        return block_codec_fn
    # One fused jit program per block size (full blocks share one entry;
    # the ragged final block compiles its own).
    base, programs = block_codec_fn, {}

    def compiled_fn(k: int) -> Codec:
        if k not in programs:
            programs[k] = compile_codec(base(k))
        return programs[k]

    return compiled_fn


class StreamEncoder:
    """Chunked streaming encoder: feed datapoints, collect wire bytes.

    ``write`` returns the bytes that became final since the last call
    (the header on first emission, then completed blocks); ``flush``
    emits any buffered ragged final block plus the end-of-stream
    trailer. Flushing twice is a no-op; writing after a flush raises.

    ``seed=None`` starts the first block cold (deterministic, right for
    direct coding); an integer seed enables random first heads and the
    per-block clean-bit supply for bits-back codecs.

    Example::

        enc = StreamEncoder(codec, lanes=16, block_symbols=64, seed=0)
        wire = enc.write(xs)      # xs [n, 16, ...]; bytes as blocks fill
        wire += enc.flush()       # ragged final block + trailer
    """

    def __init__(self, codec: Optional[Codec] = None, *, lanes: int,
                 block_symbols: int,
                 block_codec_fn: Optional[BlockCodecFn] = None,
                 seed: Optional[int] = 0, init_chunks: int = 0,
                 precision: int = ans.DEFAULT_PRECISION,
                 capacity: Optional[int] = None, max_retries: int = 6,
                 use_kernel: bool = True, compile: bool = False,
                 verify: bool = False, pipeline: bool = False):
        if lanes < 1 or block_symbols < 1:
            raise ValueError("stream: lanes and block_symbols must be >= 1")
        if seed is None and init_chunks:
            raise ValueError("stream: init_chunks requires a seed (clean "
                             "bits are derived from it)")
        self._block_codec_fn = _resolve_block_codec(codec, block_codec_fn,
                                                    use_kernel, compile)
        if verify and codec is not None:
            # Opt-in (streams are often built per connection; engines
            # verify at registration instead): check the per-symbol
            # codec's contract before any bytes hit the wire.
            from repro.analysis import check_codec
            check_codec(codec, lanes=min(lanes, 4),
                        context="StreamEncoder")
        self.lanes = lanes
        self.block_symbols = block_symbols
        self.precision = precision
        self._seed = seed
        self._init_chunks = init_chunks
        self._capacity = capacity
        self._max_retries = max_retries
        self._buffer: List[Any] = []       # pending datapoint pytrees
        self._heads: Optional[jnp.ndarray] = None   # carried across blocks
        self._pipeline = pipeline
        self._pending: Optional[_PendingBlock] = None   # in-flight block
        self._started = False
        self._finished = False
        self.n_blocks = 0
        self.n_symbols = 0
        self.net_bits = 0.0   # content added, the -ELBO-comparable rate
        self.wire_bytes = 0

    # -- input ---------------------------------------------------------------

    def write(self, data: Any) -> bytes:
        """Append time-major ``[n, lanes, ...]`` datapoints; returns any
        bytes that became final (b"" if no block completed)."""
        if self._finished:
            raise RuntimeError("stream: write after flush")
        leaves = jax.tree_util.tree_leaves(data)
        if not leaves:
            return b""
        n = leaves[0].shape[0]
        for leaf in leaves:
            if (leaf.ndim < 2 or leaf.shape[0] != n
                    or leaf.shape[1] != self.lanes):
                raise ValueError(
                    f"stream: data leaves must be [n, lanes={self.lanes}, "
                    f"...]; got {leaf.shape}")
        for t in range(n):
            self._buffer.append(
                jax.tree_util.tree_map(lambda a: a[t], data))
        out = [self._header_bytes()]
        while len(self._buffer) >= self.block_symbols:
            block, self._buffer = (self._buffer[:self.block_symbols],
                                   self._buffer[self.block_symbols:])
            if self._pipeline:
                out.append(self._encode_block_pipelined(block))
            else:
                out.append(self._encode_block(block))
        return self._emit(b"".join(out))

    def flush(self) -> bytes:
        """Emit the ragged final block (if any) and the trailer."""
        if self._finished:
            return b""
        out = [self._header_bytes()]
        if self._pending is not None:
            done, _ = self._finalize_pending()
            out.append(done)
        if self._buffer:
            block, self._buffer = self._buffer, []
            out.append(self._encode_block(block))
        out.append(fmt.encode_trailer(
            fmt.Trailer(self.n_blocks, self.n_symbols)))
        self._finished = True
        return self._emit(b"".join(out))

    def drain(self) -> bytes:
        """Finalize the in-flight block of a ``pipeline=True`` encoder.

        Returns its wire bytes (b"" when nothing is in flight). Call
        before ``snapshot`` - a pending block is not yet on the wire,
        so snapshotting over it would drop its bytes.
        """
        if self._pending is None:
            return b""
        done, _ = self._finalize_pending()
        return self._emit(done)

    @property
    def device(self):
        """The device holding the carried coder state (``None`` before
        the first block is encoded)."""
        if self._heads is None:
            return None
        (dev,) = self._heads.devices()
        return dev

    @property
    def buffered_symbols(self) -> int:
        """Datapoints accepted by ``write`` but not yet on the wire
        (zero exactly at block boundaries, where ``snapshot`` is legal)."""
        return len(self._buffer)

    # -- checkpoint / resume -------------------------------------------------

    def snapshot(self) -> EncoderSnapshot:
        """Capture resumable state at the current block boundary.

        Only legal with an empty symbol buffer (buffered datapoints are
        not yet on the wire, so a snapshot here would silently drop
        them) and before ``flush``. A ``StreamEncoder.resume``\\ d
        encoder continues the byte stream **identically** to one that
        was never interrupted - asserted by ``tests/test_gateway.py``.

        Example::

            enc = StreamEncoder(codec, lanes=4, block_symbols=8, seed=0)
            wire = enc.write(xs)              # multiple of 8 datapoints
            snap = enc.snapshot()             # ... process dies here ...
            enc2 = StreamEncoder.resume(codec, snap)
            wire += enc2.write(more) + enc2.flush()   # same bytes
        """
        if self._finished:
            raise RuntimeError("stream: snapshot after flush")
        if self._pending is not None:
            raise RuntimeError(
                "stream: snapshot with a pipelined block in flight - "
                "call drain() first (its bytes belong on the wire)")
        if self._buffer:
            raise RuntimeError(
                f"stream: snapshot mid-block ({len(self._buffer)} "
                "datapoints buffered) - write a multiple of "
                "block_symbols, or flush instead")
        heads = (tuple(int(h) for h in np.asarray(self._heads))
                 if self._heads is not None else None)
        return EncoderSnapshot(
            lanes=self.lanes, block_symbols=self.block_symbols,
            precision=self.precision, seed=self._seed,
            init_chunks=self._init_chunks, capacity=self._capacity,
            n_blocks=self.n_blocks, n_symbols=self.n_symbols,
            wire_bytes=self.wire_bytes, net_bits=self.net_bits,
            started=self._started, heads=heads)

    @classmethod
    def resume(cls, codec: Optional[Codec], snap: EncoderSnapshot,
               **kwargs) -> "StreamEncoder":
        """Rebuild an encoder from a ``snapshot()``; continuing bytes
        are identical to the uninterrupted stream. ``kwargs`` pass
        execution choices (``block_codec_fn``, ``use_kernel``,
        ``compile``) - wire bytes do not depend on them."""
        enc = cls(codec, lanes=snap.lanes,
                  block_symbols=snap.block_symbols,
                  precision=snap.precision, seed=snap.seed,
                  init_chunks=snap.init_chunks, capacity=snap.capacity,
                  **kwargs)
        enc._started = snap.started
        enc.n_blocks = snap.n_blocks
        enc.n_symbols = snap.n_symbols
        enc.wire_bytes = snap.wire_bytes
        enc.net_bits = snap.net_bits
        if snap.heads is not None:
            if len(snap.heads) != snap.lanes:
                raise ValueError(
                    f"stream: snapshot heads have {len(snap.heads)} "
                    f"lanes, expected {snap.lanes}")
            enc._heads = jnp.asarray(
                np.asarray(snap.heads, np.uint32))
        return enc

    # -- internals -----------------------------------------------------------

    def _emit(self, payload: bytes) -> bytes:
        self.wire_bytes += len(payload)
        return payload

    def _header_bytes(self) -> bytes:
        if self._started:
            return b""
        self._started = True
        return fmt.encode_header(fmt.StreamHeader(
            lanes=self.lanes, block_symbols=self.block_symbols,
            precision=self.precision))

    def _default_capacity(self, block: List[Any]) -> int:
        per_lane = sum(
            int(np.prod(leaf.shape[1:]))
            for leaf in jax.tree_util.tree_leaves(block[0]))
        return max(256, self.block_symbols * per_lane
                   + self._init_chunks + 64)

    def _block_stack(self, capacity: int, chunks: int,
                     block_index: Optional[int] = None,
                     heads: Optional[jnp.ndarray] = None) -> ans.ANSStack:
        if block_index is None:
            block_index = self.n_blocks
        if heads is None:
            heads = self._heads
        key = (jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                  block_index)
               if self._seed is not None else None)
        if heads is not None:
            stack = ans.make_stack(self.lanes, capacity)
            # Copy: a compiled block codec donates the stack it is
            # handed, which would delete the carried-heads buffer and
            # break the grow-and-retry path (and the next block) on
            # donation-honoring backends.
            stack = stack._replace(head=jnp.copy(heads))
        elif key is not None:
            k_head, _ = jax.random.split(key)
            stack = ans.make_stack(self.lanes, capacity, key=k_head)
        else:
            stack = ans.make_stack(self.lanes, capacity)
        if chunks:
            _, k_bits = jax.random.split(key)
            stack = ans.seed_stack(stack, k_bits, chunks)
        return stack

    def _push_once(self, xs: Any, k: int, cap: int, chunks: int,
                   heads: Optional[jnp.ndarray],
                   block_index: int) -> Tuple[ans.ANSStack, jnp.ndarray]:
        """Dispatch one block push; nothing here syncs with the device."""
        codec = self._block_codec_fn(k)
        stack0 = self._block_stack(cap, chunks, block_index, heads)
        # Dispatch before the push: compiled codecs donate stack0.
        bits_before = ans.stack_content_bits(stack0)
        return codec.push(stack0, xs), bits_before

    def _grow(self, over: int, under: int, cap: int,
              chunks: int) -> Tuple[int, int]:
        if over:
            cap *= 2
        if under:
            if self._seed is None:
                raise RuntimeError(
                    "stream: stack underflow with seed=None - this "
                    "codec pops initial bits (bits-back); pass a seed "
                    "so per-block clean bits can be supplied")
            chunks = max(32, chunks * 4)
        return cap, chunks

    def _commit(self, stack: ans.ANSStack, bits_before: jnp.ndarray,
                k: int, cap: int, chunks: int) -> bytes:
        self.net_bits += float(ans.stack_content_bits(stack)) \
            - float(bits_before)
        self._heads = stack.head   # carry clean bits forward
        self._capacity, self._init_chunks = cap, chunks
        msg, lengths = ans.flatten(stack)
        self.n_blocks += 1
        self.n_symbols += k
        return fmt.encode_block(k, np.asarray(msg), np.asarray(lengths))

    def _encode_sync(self, xs: Any, k: int, cap: int, chunks: int,
                     retries: int) -> bytes:
        for _ in range(retries):
            stack, bits_before = self._push_once(
                xs, k, cap, chunks, self._heads, self.n_blocks)
            over = int(jnp.sum(stack.overflows))
            under = int(jnp.sum(stack.underflows))
            if not over and not under:
                return self._commit(stack, bits_before, k, cap, chunks)
            cap, chunks = self._grow(over, under, cap, chunks)
        raise RuntimeError(
            f"stream: could not encode block cleanly after "
            f"{self._max_retries} attempts (capacity={cap}, "
            f"init_chunks={chunks})")

    def _encode_block(self, block: List[Any]) -> bytes:
        k = len(block)
        xs = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, axis=0), *block)
        cap = self._capacity or self._default_capacity(block)
        return self._encode_sync(xs, k, cap, self._init_chunks,
                                 self._max_retries)

    def _finalize_pending(self) -> Tuple[bytes, bool]:
        """Sync the in-flight block; returns (wire bytes, retried?).

        On a clean check the lazily-pushed stack is committed as-is; on
        overflow/underflow the block is redone synchronously from the
        still-valid carried heads with grown capacity/chunks, so the
        bytes are identical to what the synchronous path would emit.
        """
        pend = self._pending
        if pend is None:
            raise RuntimeError("stream: no block in flight to finalize")
        self._pending = None
        over = int(jnp.sum(pend.stack.overflows))
        under = int(jnp.sum(pend.stack.underflows))
        if not over and not under:
            return self._commit(pend.stack, pend.bits_before, pend.k,
                                pend.cap, pend.chunks), False
        cap, chunks = self._grow(over, under, pend.cap, pend.chunks)
        return self._encode_sync(pend.xs, pend.k, cap, chunks,
                                 self._max_retries - 1), True

    def _encode_block_pipelined(self, block: List[Any]) -> bytes:
        """Double-buffered block encode: dispatch block ``b+1`` against
        the lazy final heads of in-flight block ``b``, *then* pay block
        ``b``'s device sync - the new block's model compute overlaps
        it. Returns block ``b``'s bytes (b"" on the very first block).
        """
        k = len(block)
        xs = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, axis=0), *block)
        cap = self._capacity or self._default_capacity(block)
        chunks = self._init_chunks
        if self._pending is None:
            stack, bits = self._push_once(xs, k, cap, chunks,
                                          self._heads, self.n_blocks)
            self._pending = _PendingBlock(xs, k, stack, bits, cap, chunks)
            return b""
        # Optimistic dispatch: assume the in-flight block lands cleanly
        # and chain this block off its lazy heads.
        stack, bits = self._push_once(xs, k, cap, chunks,
                                      self._pending.stack.head,
                                      self.n_blocks + 1)
        done, retried = self._finalize_pending()
        if retried:
            # The in-flight block grew and re-encoded; the optimistic
            # dispatch chained off stale heads. Discard it (never
            # synced, so it cannot have left the device) and redo from
            # the corrected carried heads.
            cap = self._capacity or cap
            chunks = self._init_chunks
            stack, bits = self._push_once(xs, k, cap, chunks,
                                          self._heads, self.n_blocks)
        self._pending = _PendingBlock(xs, k, stack, bits, cap, chunks)
        return done


class StreamDecoder:
    """Incremental BBX2 decoder: feed bytes in arbitrary pieces, collect
    decoded blocks (time-major ``[k, lanes, ...]`` pytrees) as they
    complete.

    Construct with ``header=`` (e.g. from ``format.scan``) to resume
    mid-stream: the byte feed may then start at any block boundary
    instead of the stream header.

    Example::

        dec = StreamDecoder(codec)
        for piece in network_chunks:
            for block in dec.read(piece):      # [k, lanes, ...] each
                consume(block)
        assert dec.finished
    """

    def __init__(self, codec: Optional[Codec] = None, *,
                 block_codec_fn: Optional[BlockCodecFn] = None,
                 header: Optional[fmt.StreamHeader] = None,
                 use_kernel: bool = True, verify_trailer: bool = True,
                 compile: bool = False, verify: bool = False):
        self._block_codec_fn = _resolve_block_codec(codec, block_codec_fn,
                                                    use_kernel, compile)
        if verify and codec is not None:
            from repro.analysis import check_codec   # opt-in, as encoder
            check_codec(codec, lanes=4, context="StreamDecoder")
        self._header = header
        self._verify_trailer = verify_trailer
        self._buf = bytearray()
        self._finished = False
        self.n_blocks = 0
        self.n_symbols = 0
        self.trailer: Optional[fmt.Trailer] = None

    @property
    def header(self) -> Optional[fmt.StreamHeader]:
        return self._header

    @property
    def finished(self) -> bool:
        return self._finished

    def read(self, chunk: bytes = b"") -> List[Any]:
        """Feed bytes; returns the list of blocks completed by them."""
        self._buf.extend(chunk)
        out: List[Any] = []
        if self._header is None:
            parsed = fmt.decode_header(bytes(self._buf))
            if parsed is None:
                return out
            self._header, off = parsed
            del self._buf[:off]
        while not self._finished:
            res = fmt.decode_next(bytes(self._buf), 0, self._header.lanes)
            if res is None:
                break
            frame, off = res
            del self._buf[:off]
            if isinstance(frame, fmt.Trailer):
                self.trailer = frame
                self._finished = True
                if self._verify_trailer and (
                        frame.n_blocks != self.n_blocks
                        or frame.total_symbols != self.n_symbols):
                    raise ValueError(
                        f"stream: trailer mismatch (saw {self.n_blocks} "
                        f"blocks/{self.n_symbols} symbols, trailer says "
                        f"{frame.n_blocks}/{frame.total_symbols}) - "
                        "stream truncated or resumed mid-way")
                break
            out.append(self._decode_block(frame))
        return out

    def _decode_block(self, block: fmt.Block) -> Any:
        # Width-2 rows mean a chunk-less block; keep a few buffer slots
        # so bits-back decode transients (posterior re-pushes) fit.
        stack = ans.unflatten(jnp.asarray(block.msg),
                              jnp.asarray(block.lengths),
                              capacity=max(block.msg.shape[1] - 2, 8))
        codec = self._block_codec_fn(block.n_symbols)
        stack, xs = codec.pop(stack)
        under = int(jnp.sum(stack.underflows))
        over = int(jnp.sum(stack.overflows))
        if under or over:
            raise ValueError(
                f"stream: corrupt block {self.n_blocks} "
                f"({under} underflows, {over} overflows during decode)")
        self.n_blocks += 1
        self.n_symbols += block.n_symbols
        return xs


# ---------------------------------------------------------------------------
# One-call conveniences
# ---------------------------------------------------------------------------

def encode_stream(codec: Optional[Codec], data: Any, *, lanes: int,
                  block_symbols: int, **kwargs) -> bytes:
    """One-shot helper: the whole of ``data`` through a StreamEncoder.

    Example::

        wire = encode_stream(codec, xs, lanes=16, block_symbols=64)
        assert (decode_stream(codec, wire) == xs).all()
    """
    enc = StreamEncoder(codec, lanes=lanes, block_symbols=block_symbols,
                        **kwargs)
    return enc.write(data) + enc.flush()


def _concat_blocks(blocks: List[Any]) -> Any:
    if not blocks:
        return None
    return jax.tree_util.tree_map(
        lambda *ls: jnp.concatenate(ls, axis=0), *blocks)


def decode_stream(codec: Optional[Codec], blob: bytes,
                  **kwargs) -> Any:
    """Decode a complete BBX2 stream to time-major ``[n, lanes, ...]``.

    Example::

        xs = decode_stream(codec, wire)        # raises if truncated
    """
    dec = StreamDecoder(codec, **kwargs)
    blocks = dec.read(blob)
    if not dec.finished:
        raise ValueError("stream: truncated (no trailer)")
    return _concat_blocks(blocks)


def decode_from_offset(codec: Optional[Codec], blob: bytes, offset: int,
                       **kwargs) -> Any:
    """Resume decoding at a block boundary byte ``offset``.

    The stream header is read from the front of ``blob`` (it is 16
    bytes and static), then decoding starts directly at ``offset`` -
    no earlier payload byte is touched. Offsets come from
    ``format.scan`` or from bookkeeping at encode time. The trailer
    count check is skipped (a resumed decode legitimately sees fewer
    blocks than the whole stream).

    Example::

        header, offsets, trailer = stream.format.scan(wire)
        tail = decode_from_offset(codec, wire, offsets[2])  # block 2 on
    """
    parsed = fmt.decode_header(blob)
    if parsed is None:
        raise ValueError("stream: truncated (no header)")
    header, _ = parsed
    dec = StreamDecoder(codec, header=header, verify_trailer=False,
                        **kwargs)
    return _concat_blocks(dec.read(blob[offset:]))

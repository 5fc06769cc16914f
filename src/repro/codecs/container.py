"""One-call container format: ``compress(codec, data) -> bytes``.

The container owns everything callers used to hand-thread:

  * stack sizing      - starts from a heuristic capacity and
                        grows-and-retries on overflow (detected via the
                        ``ANSStack.overflows`` counter, never silent);
  * clean-bit seeding - deterministic from ``seed`` (paper section 3.2:
                        the first posterior pops consume seeded bits
                        instead of underflowing); on underflow the
                        supply is grown and the encode retried;
  * framing           - a self-describing header (magic, version,
                        precision, lanes, per-lane lengths) followed by
                        the concatenated per-lane 16-bit chunk streams,
                        so ``decompress`` needs only the codec and the
                        blob.

Wire layout (little-endian; canonical spec with invariants and a
worked example: docs/FORMATS.md):

    offset  size        field
    0       4           magic  b"BBX1"
    4       1           version (=1)
    5       1           precision (informational)
    6       2           flags (reserved, 0)
    8       4           lanes (u32)
    12      4*lanes     lengths (u32 each, in 16-bit chunks, >= 2)
    ...     2*sum(len)  payload: lane l's [head_hi, head_lo, chunks...]
"""

from __future__ import annotations

import struct
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import ans
from repro.core.codec import Codec

_MAGIC = b"BBX1"
_VERSION = 1
_HEADER = struct.Struct("<4sBBHI")
# Lanes is bounded by what a header this size can sanely describe: the
# lengths block alone is 4 bytes per lane, so anything above this is a
# corrupt count, not a real message.
_MAX_LANES = 1 << 24


class ContainerError(ValueError):
    """A blob failed header or framing validation (corrupt, truncated,
    or not a BBX1 container). Raised by ``decompress``/``blob_info``
    before any coder state is built, so corruption is reported by name
    instead of as an index error deep inside ``ans``."""


def fresh_stack(lanes: int, capacity: int, seed: Optional[int] = 0,
                init_chunks: int = 0) -> ans.ANSStack:
    """A ready-to-code stack: random heads + ``init_chunks`` clean
    16-bit chunks per lane, all derived deterministically from ``seed``.

    ``seed=None`` gives the deterministic cold stack (head = 2^16, no
    clean bits) - right for latent-free direct coding.

    Example::

        stack = fresh_stack(lanes=16, capacity=4096, seed=0,
                            init_chunks=32)   # bits-back ready
    """
    if seed is None:
        if init_chunks:
            raise ValueError(
                "fresh_stack: init_chunks requires a seed - clean bits "
                "are derived from it (pass seed=<int> or init_chunks=0)")
        stack = ans.make_stack(lanes, capacity)
    else:
        key = jax.random.PRNGKey(seed)
        k_head, k_bits = jax.random.split(key)
        stack = ans.make_stack(lanes, capacity, key=k_head)
        if init_chunks:
            stack = ans.seed_stack(stack, k_bits, init_chunks)
    return stack


def _default_capacity(data: Any, lanes: int, init_chunks: int) -> int:
    n_elems = sum(int(np.prod(x.shape))
                  for x in jax.tree_util.tree_leaves(data))
    # One 16-bit chunk per element per lane is a generous starting guess
    # for typical sub-16-bit/symbol sources; overflow-retry doubles it.
    return max(256, n_elems // max(lanes, 1) + init_chunks + 64)


def compress(codec: Codec, data: Any, *, lanes: int,
             seed: Optional[int] = 0, init_chunks: int = 32,
             capacity: Optional[int] = None, max_retries: int = 6,
             precision: int = ans.DEFAULT_PRECISION,
             with_info: bool = False
             ) -> Union[bytes, Tuple[bytes, Dict[str, Any]]]:
    """Encode ``data`` with ``codec`` into a self-contained blob.

    ``data`` is a pytree whose leaves carry a leading ``lanes`` axis
    (wrap with ``Chained`` for a [n, lanes, ...] chain). The encode is
    verified clean (no under/overflow) before the blob is emitted; on
    overflow the capacity doubles and on underflow the clean-bit supply
    quadruples, then the encode reruns - a corrupt blob is impossible.

    With ``with_info=True`` returns ``(blob, info)`` where
    ``info["net_bits"]`` is the information *added* by the encode
    (content bits after minus before - the quantity that matches -ELBO,
    free of clean-bit and flush constants).

    Example::

        codec = Chained(make_bb_codec(params, cfg), n)
        blob, info = compress(codec, data, lanes=16, seed=0,
                              with_info=True)
        rate_bpd = info["net_bits"] / data.size
    """
    cap = capacity or _default_capacity(data, lanes, init_chunks)
    # A cold stack (seed=None) has no clean-bit source; direct-coding
    # codecs don't need one, so the supply is simply 0 there.
    chunks = 0 if seed is None else init_chunks
    for attempt in range(max_retries):
        stack0 = fresh_stack(lanes, cap, seed, chunks)
        # Content bits are read *before* the push (a compiled codec
        # donates the input stack's buffers), and only when requested
        # (it costs a device reduction + host sync).
        bits_before = float(ans.stack_content_bits(stack0)) \
            if with_info else 0.0
        stack = codec.push(stack0, data)
        over = int(jnp.sum(stack.overflows))
        under = int(jnp.sum(stack.underflows))
        if not over and not under:
            blob = _pack(stack, precision)
            if not with_info:
                return blob
            info = {
                "capacity": cap, "init_chunks": chunks, "seed": seed,
                "net_bits": float(ans.stack_content_bits(stack))
                - bits_before,
                "retries": attempt,
                **blob_info(blob),
            }
            return blob, info
        if over:
            cap *= 2
        if under:
            if seed is None:
                raise RuntimeError(
                    "codecs.compress: stack underflow with seed=None - "
                    "this codec pops initial bits (bits-back); pass a "
                    "seed so clean bits can be supplied")
            chunks = max(32, chunks * 4)
    raise RuntimeError(
        f"codecs.compress: could not encode cleanly after {max_retries} "
        f"attempts (last capacity={cap}, init_chunks={chunks})")


def decompress(codec: Codec, blob: bytes) -> Any:
    """Decode a ``compress`` blob back to the original data, bit-exactly.

    Example::

        assert (decompress(codec, compress(codec, data, lanes=16))
                == data).all()
    """
    msg, lengths, _ = _unpack(blob)
    stack = ans.unflatten(jnp.asarray(msg), jnp.asarray(lengths))
    stack, data = codec.pop(stack)
    ans.check_clean(stack, "codecs.decompress")
    return data


def blob_info(blob: bytes) -> Dict[str, Any]:
    """Parse a blob header: lanes, lengths, payload/header sizes in bits.

    ``payload_bits`` equals ``ans.stack_bits`` of the encoded stack -
    the message proper; ``header_bits`` is the framing overhead.

    Example::

        info = blob_info(blob)
        overhead = info["header_bits"] / info["total_bits"]

    Byte-level layout: docs/FORMATS.md.
    """
    msg, lengths, precision = _unpack(blob)
    payload_bits = int(np.sum(lengths)) * 16
    return {
        "lanes": int(msg.shape[0]),
        "lengths": lengths,
        "precision": precision,
        "payload_bits": payload_bits,
        "header_bits": (len(blob) - payload_bits // 8) * 8,
        "total_bits": len(blob) * 8,
    }


def pack_lane_rows(msg: np.ndarray, lengths: np.ndarray) -> bytes:
    """Concatenate per-lane ``msg[l, :lengths[l]]`` rows into wire bytes.

    The shared payload primitive of the BBX1 one-shot container and the
    ``repro.stream`` BBX2 block format (little-endian u16 chunks).
    """
    msg = np.asarray(msg)
    lengths = np.asarray(lengths)
    return b"".join(msg[l, :lengths[l]].astype("<u2").tobytes()
                    for l in range(msg.shape[0]))


def unpack_lane_rows(buf: bytes, offset: int,
                     lengths: np.ndarray) -> np.ndarray:
    """Inverse of ``pack_lane_rows``: rebuild the padded [lanes, width]
    uint16 message from concatenated rows at ``offset`` in ``buf``."""
    lengths = np.asarray(lengths)
    total = int(lengths.sum())
    if len(buf) < offset + 2 * total:
        raise ValueError("codecs: truncated payload (lane rows short)")
    flat = np.frombuffer(buf, dtype="<u2", count=total, offset=offset)
    width = int(lengths.max()) if lengths.size else 0
    msg = np.zeros((lengths.shape[0], width), np.uint16)
    pos = 0
    for l in range(lengths.shape[0]):
        n = int(lengths[l])
        msg[l, :n] = flat[pos:pos + n]
        pos += n
    return msg


def _pack(stack: ans.ANSStack, precision: int) -> bytes:
    msg, lengths = ans.flatten(stack)
    msg_np = spans.host_read(msg, "container.msg")
    lengths_np = spans.host_read(lengths, "container.lengths")
    lanes = msg_np.shape[0]
    return b"".join([
        _HEADER.pack(_MAGIC, _VERSION, precision, 0, lanes),
        lengths_np.astype("<u4").tobytes(),
        pack_lane_rows(msg_np, lengths_np),
    ])


def _unpack(blob: bytes) -> Tuple[np.ndarray, np.ndarray, int]:
    if len(blob) < _HEADER.size:
        raise ContainerError("codecs: truncated blob (no header)")
    magic, version, precision, _flags, lanes = _HEADER.unpack_from(blob, 0)
    if magic != _MAGIC:
        raise ContainerError(
            f"codecs: bad magic {magic!r} (not a BBX1 blob)")
    if version != _VERSION:
        raise ContainerError(
            f"codecs: unsupported container version {version}")
    if not 0 < precision <= ans.MAX_PRECISION:
        raise ContainerError(
            f"codecs: corrupt header (precision {precision} outside "
            f"[1, {ans.MAX_PRECISION}])")
    if not 0 < lanes <= _MAX_LANES:
        raise ContainerError(
            f"codecs: corrupt header (lane count {lanes})")
    off = _HEADER.size
    if len(blob) < off + 4 * lanes:
        raise ContainerError(
            f"codecs: truncated blob (header promises {lanes} lane "
            "lengths but the lengths block is short)")
    lengths = np.frombuffer(blob, dtype="<u4", count=lanes,
                            offset=off).astype(np.int64)
    if (lengths < 2).any():
        raise ContainerError("codecs: corrupt header (lane length < 2; "
                             "every lane carries a 2-chunk head flush)")
    off += 4 * lanes
    payload = len(blob) - off
    need = 2 * int(lengths.sum())
    if payload != need:
        raise ContainerError(
            f"codecs: payload is {payload} bytes but the lane lengths "
            f"sum to {need} (truncated or trailing garbage)")
    msg = unpack_lane_rows(blob, off, lengths.astype(np.int32))
    return msg, lengths.astype(np.int32), precision

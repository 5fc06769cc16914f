"""``repro.shard_codec`` - dataset-scale lane-parallel coding across
devices.

The paper closes on BB-ANS being "highly amenable to parallelization";
this module is that claim operationalized at dataset scale. The lane
axis of the ``ANSStack`` is already N independent coders, so the
execution model is pure data parallelism over lanes, in two forms
(docs/SCALING.md is the narrative spec):

  * **Sharded segments** (this module): the lane axis is cut into
    ``n_shards`` contiguous shards; each shard streams its datapoints
    through its own ``stream.StreamEncoder`` with its arrays placed on
    its own device, producing one independently-decodable BBX2 segment;
    the segments are gathered into a single ``BBX3`` corpus blob
    (``stream.format``: header + index + segments). Decode mirrors:
    any shard - or all of them - decodes from its segment alone, so a
    cluster can fan the corpus out by index entry.
  * **SPMD coder programs** (``codecs.compile`` + ``sharding.api``):
    under ``sharding.use_lane_mesh``, compiled codecs run their fused
    integer coder calls through ``shard_map`` over a 1-D device mesh -
    one logical stack, lanes split across devices, byte-identical wire.
    ``serve.ShardedCodecEngine`` uses this for its one-shot path.

Both forms hold the PR-4 determinism contract across devices: wire
bytes depend only on (codec, data, shard layout), never on the
physical device count or placement - integer coder ops are exact in
any partitioning, and model floats keep evaluating in canonical eager
form per shard. ``tests/test_shard_codec.py`` proves byte-identity
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    blob = shard_codec.compress_dataset(codec, data, n_shards=8)
    data2 = shard_codec.decompress_dataset(codec, blob)      # bit-exact
    xs3 = shard_codec.decompress_shard(codec, blob, shard=3)  # just one

The dataset CLI driving this end to end (full synthetic-MNIST through
a trained VAE/HVAE, Table-1 comparison vs gzip/bz2/PNG-proxy) is
``python -m repro.launch.compress``.
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Any, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro import stream
from repro.core import ans
from repro.core.codec import Codec
from repro.kernels import dispatch
from repro.stream import format as fmt

__all__ = [
    "shard_devices", "split_lane_tree", "merge_lane_tree",
    "compress_dataset", "decompress_dataset", "decompress_shard",
    "corpus_info",
]


def shard_devices(n_shards: int) -> List[Any]:
    """Device for each shard: local devices, round-robin.

    With fewer devices than shards, several shards share a device (the
    single-device case degenerates to all of them - bytes unchanged,
    see the determinism note in the module docstring).

    Example::

        devs = shard_devices(8)        # 8 entries, cycling jax.devices()
    """
    if n_shards < 1:
        raise ValueError("shard_codec: n_shards must be >= 1")
    local = jax.devices()
    return [local[s % len(local)] for s in range(n_shards)]


def _lane_count(data: Any) -> int:
    leaves = jax.tree_util.tree_leaves(data)
    if not leaves:
        raise ValueError("shard_codec: empty data pytree")
    return leaves[0].shape[1]


def split_lane_tree(data: Any, n_shards: int) -> List[Any]:
    """Split time-major ``[n, lanes, ...]`` data into ``n_shards``
    contiguous lane slices (the data twin of ``ans.split_lanes``).

    Example::

        shards = split_lane_tree(xs, 4)     # each [n, lanes/4, ...]
    """
    lanes = _lane_count(data)
    if n_shards < 1 or lanes % n_shards:
        raise ValueError(
            f"shard_codec: {lanes} lanes do not divide into "
            f"{n_shards} equal shards")
    per = lanes // n_shards
    return [jax.tree_util.tree_map(
        lambda a: a[:, s * per:(s + 1) * per], data)
        for s in range(n_shards)]


def merge_lane_tree(shards: Sequence[Any]) -> Any:
    """Concatenate per-shard ``[n, lanes_s, ...]`` trees back along the
    lane axis (inverse of ``split_lane_tree``).

    Example::

        assert (merge_lane_tree(split_lane_tree(xs, 4)) == xs).all()
    """
    shards = list(shards)
    if not shards:
        raise ValueError("shard_codec: no shards to merge")
    return jax.tree_util.tree_map(
        lambda *ls: jnp.concatenate(ls, axis=1), *shards)


def peek_chunks(data: Any) -> Tuple[Any, Iterable[Any]]:
    """Normalize ``data`` to ``(first_chunk, iterable of chunks)``.

    Lists and iterators are treated as streams of ``[n, lanes, ...]``
    chunks (the loader case); anything else (array, dict/tuple pytree)
    is a single chunk. The first chunk is peeked - without losing it
    from the stream - so callers can size shards/codecs before
    encoding starts. Raises ``ValueError`` on an empty stream. Shared
    by ``compress_dataset`` and ``serve.ShardedCodecEngine``.
    """
    empty = "shard_codec: no data chunks to compress"
    if isinstance(data, list):
        if not data:
            raise ValueError(empty)
        return data[0], data
    if hasattr(data, "__next__"):
        try:
            first = next(data)
        except StopIteration:
            raise ValueError(empty) from None
        return first, itertools.chain([first], data)
    return data, [data]


def _backend_ctx(kernel_backend: Optional[str]):
    """``dispatch.use_backend`` pin for one corpus pass (no-op when
    ``None``: each coder op auto-resolves via the tuning cache /
    platform heuristic - wire bytes are the same either way)."""
    if kernel_backend is None:
        return contextlib.nullcontext()
    return dispatch.use_backend(kernel_backend)


def compress_dataset(codec: Codec, data: Any, *, n_shards: int,
                     block_symbols: int = 8,
                     seed: Optional[int] = 0, init_chunks: int = 32,
                     precision: int = ans.DEFAULT_PRECISION,
                     devices: Optional[Sequence[Any]] = None,
                     kernel_backend: Optional[str] = None,
                     with_info: bool = False,
                     **encoder_kwargs) -> Union[bytes, Tuple[bytes, dict]]:
    """Compress a dataset to one BBX3 corpus blob, lane-parallel.

    ``data`` is a ``[n, lanes, ...]`` pytree or an iterable of such
    chunks (a streaming loader); ``lanes`` must divide into
    ``n_shards``. Each shard's slice is placed on its device
    (``shard_devices`` by default) and encoded by its own
    ``StreamEncoder`` - the shards' device work overlaps through JAX's
    async dispatch, and the resulting wire bytes depend only on
    (codec, data, n_shards, block_symbols, seed), never on how many
    physical devices the shards landed on.

    ``seed=None`` runs every shard cold (direct coding); an integer
    seed gives shard ``s`` the derived seed ``seed + s`` for its random
    first heads and per-block clean bits. Extra ``encoder_kwargs``
    (``capacity``, ``compile``, ...) pass through to every encoder.

    ``with_info=True`` returns ``(blob, info)``: ``info["net_bits"]``
    is the information the encode added (the -ELBO-comparable rate,
    free of clean-bit and flush constants) and
    ``info["shard_devices"]`` the device each shard's coder state
    ended on.

    Example::

        blob = compress_dataset(codec, xs, n_shards=4, block_symbols=8)
        assert (decompress_dataset(codec, blob) == xs).all()
    """
    first, chunks = peek_chunks(data)
    lanes = _lane_count(first)
    if lanes % n_shards:
        raise ValueError(
            f"shard_codec: {lanes} lanes do not divide into "
            f"{n_shards} equal shards")
    devs = list(devices) if devices is not None \
        else shard_devices(n_shards)
    if len(devs) != n_shards:
        raise ValueError(f"shard_codec: got {len(devs)} devices for "
                         f"{n_shards} shards")
    with _backend_ctx(kernel_backend):
        encoders = [stream.StreamEncoder(
            codec, lanes=lanes // n_shards, block_symbols=block_symbols,
            seed=None if seed is None else seed + s,
            init_chunks=init_chunks, precision=precision,
            **encoder_kwargs) for s in range(n_shards)]
        segments = [bytearray() for _ in range(n_shards)]
        for chunk in chunks:
            for s, shard in enumerate(split_lane_tree(chunk, n_shards)):
                placed = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, devs[s]), shard)
                segments[s].extend(encoders[s].write(placed))
        for s, enc in enumerate(encoders):
            segments[s].extend(enc.flush())
    blob = fmt.encode_corpus(
        [bytes(seg) for seg in segments],
        [enc.n_symbols for enc in encoders],
        lanes_per_shard=encoders[0].lanes, precision=precision)
    if not with_info:
        return blob
    return blob, {"net_bits": sum(enc.net_bits for enc in encoders),
                  "shard_devices": [enc.device for enc in encoders]}


def decompress_shard(codec: Codec, blob: bytes, shard: int,
                     kernel_backend: Optional[str] = None,
                     **decoder_kwargs) -> Any:
    """Decode ONE shard of a BBX3 corpus - no other shard's bytes are
    touched (the unit of distributed decode).

    Example::

        xs3 = decompress_shard(codec, blob, 3)   # [n, lanes_per_shard, ...]
    """
    with _backend_ctx(kernel_backend):
        return stream.decode_stream(codec, fmt.corpus_segment(blob, shard),
                                    **decoder_kwargs)


def decompress_dataset(codec: Codec, blob: bytes, *,
                       devices: Optional[Sequence[Any]] = None,
                       kernel_backend: Optional[str] = None,
                       **decoder_kwargs) -> Any:
    """Decode a whole BBX3 corpus back to ``[n, lanes, ...]``,
    bit-exactly, shard by shard (each independently, on its own
    device by default).

    Example::

        xs = decompress_dataset(codec, compress_dataset(
            codec, xs, n_shards=4))
    """
    header, entries = fmt.scan_corpus(blob)
    devs = list(devices) if devices is not None \
        else shard_devices(header.n_shards)
    outs = []
    with _backend_ctx(kernel_backend):
        for s, e in enumerate(entries):
            seg = blob[e.offset:e.offset + e.length]
            with jax.default_device(devs[s % len(devs)]):
                outs.append(stream.decode_stream(codec, seg,
                                                 **decoder_kwargs))
    return merge_lane_tree(outs)


def corpus_info(blob: bytes) -> dict:
    """Summarize a BBX3 corpus from framing alone: shard count, lane
    layout, per-shard byte/symbol totals.

    Example::

        info = corpus_info(blob)
        assert info["n_shards"] == len(info["shard_bytes"])
    """
    header, entries = fmt.scan_corpus(blob)
    return {
        "n_shards": header.n_shards,
        "lanes_per_shard": header.lanes_per_shard,
        "precision": header.precision,
        "total_bytes": len(blob),
        "index_bytes": fmt.CORPUS_HEADER_SIZE
        + header.n_shards * fmt.CORPUS_ENTRY_SIZE,
        "shard_bytes": [e.length for e in entries],
        "shard_symbols": [e.n_symbols for e in entries],
        "total_symbols": sum(e.n_symbols for e in entries),
    }

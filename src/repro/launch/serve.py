"""Serving/compression launcher.

``python -m repro.launch.serve --arch qwen2-0.5b --mode compress``
trains nothing: it builds a (reduced) model, runs the compression
service end to end on a synthetic corpus and reports rates; ``--mode
stream`` runs the chunked BBX2 streaming path (and verifies a
mid-stream resume); ``--mode serve-many`` drives the dynamic batcher
over many requests of different lengths; ``--mode generate`` runs
batched greedy decoding; ``--mode hvae`` serves the hierarchical image
codec through ``serve.CodecEngine`` at several image shapes from one
parameter set; ``--mode gateway`` drives concurrent ragged clients
through the async ``repro.gateway`` tier (admission, backpressure,
recovery); ``--mode cluster`` spreads clients and a BBX3 corpus across
a multi-host ``GatewayCluster`` (each host on its own event loop,
engines attached from ``EngineHandle`` recipes), kills one host
mid-stream, and verifies the failed-over wires stay byte-identical.
The same Engine runs on pod meshes via the dryrun-validated
decode/prefill programs.

Shutdown is clean: open ``StreamEncoder``s register themselves, and a
SIGINT mid-stream flushes each one (ragged tail + valid BBX2 trailer)
before the process exits, so an interrupted run never leaves a
truncated wire.
"""

from __future__ import annotations

import argparse
import dataclasses
import signal
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs, stream
from repro.configs import base as cfg_base
from repro.data import tokens as tok_data
from repro.launch import compile_cache
from repro.models import transformer
from repro.serve.engine import Engine

# Open streaming encoders, flushed on SIGINT so every wire ends in a
# valid BBX2 trailer (satellite of the gateway PR; see module docstring).
_OPEN_ENCODERS: Dict[str, stream.StreamEncoder] = {}


def flush_open_encoders() -> Dict[str, bytes]:
    """Flush every registered open ``StreamEncoder`` (ragged tail +
    trailer) and deregister it; returns ``{name: tail_bytes}``. Safe to
    call twice - a flushed encoder is removed, and ``flush`` on an
    already-finished encoder is a no-op anyway."""
    tails: Dict[str, bytes] = {}
    for name in list(_OPEN_ENCODERS):
        tails[name] = _OPEN_ENCODERS.pop(name).flush()
    return tails


def install_sigint_flush():
    """Install a SIGINT handler that flushes open encoders before
    re-raising ``KeyboardInterrupt``. Returns the handler (tests call
    it directly). The previous handler is restored after one firing."""
    prev = signal.getsignal(signal.SIGINT)

    def handler(signum=signal.SIGINT, frame=None):
        tails = flush_open_encoders()
        if tails:
            total = sum(len(t) for t in tails.values())
            print(f"\nSIGINT: flushed {len(tails)} open stream(s) to "
                  f"valid trailers (+{total} bytes)")
        signal.signal(signal.SIGINT, prev)
        raise KeyboardInterrupt

    signal.signal(signal.SIGINT, handler)
    return handler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--mode", default="compress",
                    choices=["compress", "stream", "serve-many",
                             "generate", "hvae", "gateway", "cluster"])
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--block-symbols", type=int, default=16)
    ap.add_argument("--requests", type=int, default=12,
                    help="number of client streams for --mode serve-many")
    ap.add_argument("--hosts", type=int, default=2,
                    help="gateway hosts for --mode cluster")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile", action="store_true",
                    help="route codecs through codecs.compile (fused "
                         "kernel programs; byte-identical wire)")
    ap.add_argument("--kv-dtype", default="bfloat16")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()

    if args.mode == "hvae":
        return main_hvae(args)
    if args.mode == "gateway":
        return main_gateway(args)
    if args.mode == "cluster":
        return main_cluster(args)

    cfg = dataclasses.replace(
        cfg_base.reduced(cfg_base.get(args.arch)),
        vocab=256, kv_cache_dtype=args.kv_dtype)
    params = transformer.init(jax.random.PRNGKey(args.seed), cfg)
    eng = Engine(params, cfg, max_len=args.tokens, jit=False)

    if args.mode == "generate":
        prompt = {"tokens": jnp.asarray(
            np.random.default_rng(args.seed).integers(
                0, cfg.vocab, (args.lanes, 8)), jnp.int32)}
        t0 = time.perf_counter()
        out = eng.generate(prompt, args.tokens)
        dt = time.perf_counter() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({out.size / dt:.1f} tok/s, untrained weights)")
        return

    corpus, entropy = tok_data.markov_corpus(
        50_000, vocab=cfg.vocab, seed=args.seed)
    rng = np.random.default_rng(args.seed + 1)

    if args.mode == "serve-many":
        reqs = []
        for _ in range(args.requests):
            n = int(rng.integers(4, args.tokens + 1))
            s = int(rng.integers(0, len(corpus) - n))
            reqs.append(jnp.asarray(corpus[s:s + n], jnp.int32))
        t0 = time.perf_counter()
        blobs = eng.serve_many(reqs, max_lanes=args.lanes,
                               block_symbols=args.block_symbols)
        enc = time.perf_counter() - t0
        outs = eng.decompress_many(blobs, max_lanes=args.lanes,
                                   block_symbols=args.block_symbols)
        ok = all(bool(jnp.array_equal(o, r)) for o, r in zip(outs, reqs))
        total = sum(int(r.size) for r in reqs)
        bits = sum(len(b) * 8 for b in blobs)
        print(f"served {len(reqs)} streams ({total} tokens) through "
              f"{args.lanes} lanes in {enc:.2f}s; {bits / total:.3f} "
              f"wire bits/tok (untrained model: ~log2 V); lossless={ok}")
        return

    starts = rng.integers(0, len(corpus) - args.tokens, args.lanes)
    toks = jnp.asarray(
        np.stack([corpus[s:s + args.tokens] for s in starts]), jnp.int32)

    if args.mode == "stream":
        install_sigint_flush()
        t0 = time.perf_counter()
        # Built explicitly (same parameters as Engine.compress_stream)
        # and registered, so a SIGINT mid-write flushes to a valid
        # trailer instead of leaving a truncated wire.
        encoder = stream.StreamEncoder(
            block_codec_fn=eng._block_codec_fn(), lanes=args.lanes,
            block_symbols=args.block_symbols, seed=None,
            capacity=int(args.block_symbols * 1.5) + 8)
        _OPEN_ENCODERS["stream"] = encoder
        blob = encoder.write(toks.T) + encoder.flush()
        _OPEN_ENCODERS.pop("stream", None)
        enc = time.perf_counter() - t0
        header, offsets, trailer = stream.format.scan(blob)
        out = eng.decompress_stream(blob)
        ok = bool(jnp.array_equal(out, toks))
        print(f"corpus entropy {entropy:.3f} bits/tok; streamed "
              f"{len(blob) * 8 / toks.size:.3f} wire bits/tok over "
              f"{len(offsets)} blocks; lossless={ok}; encode {enc:.2f}s")
        if len(offsets) > 1:
            tail = stream.decode_from_offset(
                None, blob, offsets[1],
                block_codec_fn=eng._block_codec_fn())
            ok2 = bool(jnp.array_equal(
                tail.T, toks[:, args.block_symbols:]))
            print(f"mid-stream resume from block 1 "
                  f"(byte {offsets[1]}): lossless={ok2}")
        return

    t0 = time.perf_counter()
    blob = eng.compress(toks)
    enc = time.perf_counter() - t0
    bits = codecs.blob_info(blob)["payload_bits"]
    out = eng.decompress(blob, args.tokens)
    ok = bool(jnp.array_equal(out, toks))
    print(f"corpus entropy {entropy:.3f} bits/tok; achieved "
          f"{bits / toks.size:.3f} bits/tok (untrained model: ~log2 V); "
          f"lossless={ok}; encode {enc:.2f}s")


def main_gateway(args):
    """Async serving demo: ragged concurrent clients stream through the
    ``repro.gateway`` admission tier over one ``CodecEngine`` (toy
    uniform family - the point here is scheduling, not the model).
    SIGINT flushes open sessions to valid trailers before exit."""
    import asyncio

    from repro import gateway as gw_mod
    from repro.serve import CodecEngine

    def family(shape):
        n = int(np.prod(shape))
        return codecs.Shaped(
            codecs.Repeat(lambda d: codecs.Uniform(8), n), tuple(shape))

    shape, lanes = (4, 4), args.lanes
    eng = CodecEngine(family, seed=args.seed, init_chunks=0,
                      max_inflight_lanes=2 * lanes,
                      compile=args.compile)
    rng = np.random.default_rng(args.seed)

    async def client(gw, i: int):
        n_blocks = int(rng.integers(2, 6))
        data = jnp.asarray(rng.integers(
            0, 256, (n_blocks * args.block_symbols, lanes, *shape)),
            jnp.int32)
        sess = await gw.open_stream(shape, lanes=lanes,
                                    session_id=f"client-{i}",
                                    tenant=f"tenant-{i % 2}",
                                    block_symbols=args.block_symbols)
        wire = await sess.write(data)
        wire += await sess.close()
        out = eng.decompress_stream(wire, shape)
        if not bool(jnp.array_equal(out, data)):
            raise SystemExit(f"client {i}: lossy round trip")
        return len(wire), int(data.size)

    async def run():
        async with gw_mod.Gateway(eng, queue_depth=args.requests) as gw:
            loop = asyncio.get_running_loop()
            stop = asyncio.Event()
            try:
                loop.add_signal_handler(signal.SIGINT, stop.set)
            except NotImplementedError:
                pass   # non-Unix event loop
            work = asyncio.gather(*(client(gw, i)
                                    for i in range(args.requests)))
            stopper = asyncio.create_task(stop.wait())
            done, _ = await asyncio.wait(
                {work, stopper}, return_when=asyncio.FIRST_COMPLETED)
            if work in done:
                stopper.cancel()
                sizes = work.result()
                wire = sum(s for s, _ in sizes)
                syms = sum(n for _, n in sizes)
                print(f"gateway served {len(sizes)} clients: "
                      f"{wire * 8 / syms:.3f} wire bits/dim, "
                      f"stats={gw.stats()}")
            else:
                work.cancel()
                tails = await gw.stop()
                print(f"SIGINT: flushed {len(tails)} open session(s) "
                      "to valid trailers")

    asyncio.run(run())


def main_cluster(args):
    """Multi-host serving demo: ``--hosts`` gateways, each with its own
    event loop and an engine attached from an ``EngineHandle`` recipe.
    Ragged clients and a sharded BBX3 corpus spread across the hosts,
    one host is killed mid-stream, and every failed-over wire is
    checked byte-identical to the synchronous single-host path."""
    import asyncio
    import tempfile

    from repro import shard_codec
    from repro.gateway import GatewayCluster, TenantQuota
    from repro.serve import CodecEngine, EngineHandle, \
        register_engine_factory

    def family(shape):
        n = int(np.prod(shape))
        return codecs.Shaped(
            codecs.Repeat(lambda d: codecs.Uniform(8), n), tuple(shape))

    register_engine_factory(
        "launch-cluster-uniform",
        lambda **kw: CodecEngine(family, **kw), overwrite=True)
    shape, lanes = (4, 4), args.lanes
    handle = EngineHandle("launch-cluster-uniform",
                          {"seed": args.seed, "init_chunks": 0,
                           "max_inflight_lanes": 8 * lanes,
                           "compile": args.compile})
    rng = np.random.default_rng(args.seed)
    ref_eng = CodecEngine(family, seed=args.seed, init_chunks=0,
                          max_inflight_lanes=8 * lanes,
                          compile=args.compile)
    corpora = [jnp.asarray(rng.integers(
        0, 256, (int(rng.integers(2, 5)) * args.block_symbols, lanes,
                 *shape)), jnp.int32) for _ in range(args.requests)]
    refs = [ref_eng.compress_stream(d, block_symbols=args.block_symbols)
            for d in corpora]
    ds = jnp.asarray(rng.integers(
        0, 256, (2 * args.block_symbols, 2 * lanes, *shape)), jnp.int32)
    ds_ref = shard_codec.compress_dataset(
        family(shape), ds, n_shards=2, seed=args.seed, init_chunks=0,
        block_symbols=args.block_symbols)

    async def client(cluster, i: int):
        data = corpora[i]
        sess = await cluster.open_stream(
            shape, lanes=lanes, session_id=f"client-{i}",
            tenant=f"tenant-{i % 2}",
            block_symbols=args.block_symbols)
        wire = b""
        for s in range(0, int(data.shape[0]), args.block_symbols):
            wire += await sess.write(data[s:s + args.block_symbols])
            if i == 0 and s == 0:
                # One deterministic mid-stream kill: whichever host
                # serves client 0 dies after its first block.
                await cluster.kill_host(sess.host)
        wire += await sess.close()
        if wire != refs[i]:
            raise SystemExit(f"client {i}: cluster wire diverged")
        return len(wire), int(data.size), sess.failovers

    async def run(tmp: str):
        cluster = GatewayCluster(
            [handle] * args.hosts, loop_per_host=True,
            recovery_root=tmp, queue_depth=args.requests,
            default_quota=TenantQuota(max_lanes=8 * lanes,
                                      max_queued=args.requests))
        async with cluster:
            results = await asyncio.gather(
                *(client(cluster, i) for i in range(args.requests)))
            blob = await cluster.compress_corpus(
                ds, n_shards=2, seed=args.seed, init_chunks=0,
                block_symbols=args.block_symbols, tag="launch-corpus")
            if blob != ds_ref:
                raise SystemExit("cluster corpus wire diverged")
            out = await cluster.decompress_corpus(blob, shape)
            if not bool(jnp.array_equal(out, ds)):
                raise SystemExit("cluster corpus round trip lossy")
            st = cluster.stats()
        wire = sum(w for w, _, _ in results)
        syms = sum(n for _, n, _ in results)
        fails = sum(f for _, _, f in results)
        print(f"cluster served {len(results)} clients + 1 corpus over "
              f"{args.hosts} hosts ({len(st['healthy_hosts'])} "
              f"survived a mid-stream kill): {wire * 8 / syms:.3f} "
              f"wire bits/dim, {fails} stream failover(s), all wires "
              "byte-identical to single-host")
        print(f"stats={st}")
        if st["cluster_held_lanes"] or st["inflight_lanes"]:
            raise SystemExit("lane leak after drain")

    with tempfile.TemporaryDirectory() as tmp:
        asyncio.run(run(tmp))


def main_hvae(args):
    """Image-codec service demo: one fully convolutional model, several
    request shapes, one-shot + streaming wire paths, all lossless."""
    from repro.configs import hvae_img
    from repro.data import images as img_data
    from repro.models import hvae
    from repro.serve.engine import CodecEngine

    cfg = hvae_img.get("hvae-small2")
    params = hvae.init(jax.random.PRNGKey(args.seed), cfg)
    eng = CodecEngine(hvae.codec_family(params, cfg), seed=args.seed,
                      compile=args.compile)
    lanes = args.lanes
    for shape in ((16, 16), (20, 12)):
        raw = img_data.load("test", 2 * lanes, args.seed, hw=shape)
        data = jnp.asarray(raw.reshape(2, lanes, *shape), jnp.int32)
        t0 = time.perf_counter()
        blob = eng.compress(data)
        enc = time.perf_counter() - t0
        ok = bool(jnp.array_equal(eng.decompress(blob, 2, shape), data))
        wire = eng.compress_stream(data, block_symbols=1)
        ok2 = bool(jnp.array_equal(eng.decompress_stream(wire, shape),
                                   data))
        print(f"{shape[0]}x{shape[1]}: one-shot {len(blob) * 8 / data.size:.2f} "
              f"wire bits/dim (untrained), lossless={ok}; "
              f"stream lossless={ok2}; encode {enc:.2f}s")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Chip smoke test: the BB-ANS compress/decompress main path on a TPU.

Runs the paper's system once, end to end, in one process, through the
functions its entry points use (``repro.launch.compress``,
``repro.shard_codec``, ``repro.serve``):

  1. train the paper's VAE (784-100-40, Bernoulli likelihood,
     ``lat_bits=10``) on synthetic MNIST and report its test -ELBO;
  2. compress the Table-1 corpus - 1024 lanes x 4 chained images -
     through ``shard_codec.compress_dataset`` / ``decompress_dataset``
     with the codec compiler on: the decode must be lossless and the
     coded rate within a few percent of -ELBO;
  3. serve requests through ``serve.CodecEngine(compile=True)`` over
     the fused fixed-point codec: one-shot BBX1 requests and one BBX2
     stream, each decoded losslessly;
  4. report the backend every hot coder op resolved to: all ``pallas``
     (Mosaic-compiled), none ``interpret``;
  5. encode under the ``pallas`` kernels and decode under the ``xla``
     twins, and the other way round: identical bytes, lossless decodes;
  6. decode the committed golden blobs that decode on the CPU.

``--chips 4`` runs only the paths that span chips, each against the
same encode on one device: ``compress_dataset`` with 4 shards on 4
devices, and the ``ShardedCodecEngine`` one-shot path under a 4-device
lane mesh (1024 lanes x 1 image, random VAE weights from the seed; no
training).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

Earlier lines report the device, each phase, the compile (set-up)
seconds and the wall seconds; the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or without the ``repro`` package next to it, the script
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

LANES = 1024          # ANS lanes: images coded side by side
CHAIN = 4             # images chained per lane
TRAIN_STEPS = 300
SEED = 0
PIXELS = (784,)
GOLDEN = ("bbx1_uniform", "bbx1_categorical", "bbx3_cluster")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def same(a, b) -> bool:
    import jax.numpy as jnp
    return bool(jnp.array_equal(jnp.asarray(a), jnp.asarray(b)))


class CompileTimer:
    """Seconds JAX spends tracing, lowering and compiling; the part of
    them spent in the backend compiler (what a persistent-cache hit
    saves) and its number of programs; the persistent-cache hits."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._BACKEND:
            self.backend_seconds += duration
            self.programs += 1
        if event in self._EVENTS or event == self._BACKEND:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def record_backends() -> dict:
    """Record every ``dispatch.resolve`` answer of the run: op -> the
    set of backends it resolved to."""
    from repro.kernels import dispatch

    seen: dict = {}
    resolve = dispatch.resolve

    def recording(op, *args, **kwargs):
        decision = resolve(op, *args, **kwargs)
        seen.setdefault(op, set()).add(decision.backend)
        return decision

    dispatch.resolve = recording
    return seen


def timed(name: str):
    """Decorator: log a phase's wall seconds after it passes."""
    def wrap(fn):
        def run(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            log(f"{name}: ok ({time.perf_counter() - t0:.1f} s, "
                "compile included)")
            return out
        return run
    return wrap


def fused_engine(params, cfg, **kwargs):
    from repro.models import vae
    from repro.serve import CodecEngine
    return CodecEngine(
        lambda shape: vae.make_bb_codec_q(params, cfg, compiled=True),
        seed=SEED, compile=True, **kwargs)


@timed("train")
def phase_train():
    from repro.launch import compress as cli
    from repro.models import vae

    cfg = vae.paper_config("bernoulli")
    params, elbo = cli.train_vae(cfg, steps=TRAIN_STEPS, seed=SEED)
    log(f"train: {TRAIN_STEPS} steps, test -ELBO = {elbo:.4f} bits/dim")
    check(math.isfinite(elbo) and elbo > 0.0,
          f"train: -ELBO of {elbo} bits/dim")
    return cfg, params, elbo


@timed("corpus")
def phase_corpus(cfg, params, elbo, imgs, data):
    import jax
    import jax.numpy as jnp

    from repro import shard_codec
    from repro.launch import compress as cli
    from repro.models import vae

    codec = vae.make_bb_codec(params, cfg)
    blob, info = cli.compress_corpus(codec, data, n_shards=1,
                                     block_symbols=CHAIN, seed=SEED,
                                     with_info=True)
    out = shard_codec.decompress_dataset(codec, blob, compile=True)
    check(same(out, data), "corpus: the decode is not lossless")
    wire = len(blob) * 8 / imgs.size
    net = info["net_bits"] / imgs.size
    # -ELBO of these very images (4 posterior samples, as in training).
    keys = jax.random.split(jax.random.PRNGKey(SEED + 3), 4)
    corpus_elbo = sum(float(vae.elbo_bits_per_dim(
        params, cfg, k, jnp.asarray(imgs, jnp.int32))) for k in keys) / 4
    log(f"corpus: {imgs.shape[0]} images, {len(blob)} wire bytes, "
        f"lossless; wire {wire:.4f} bits/dim (clean-bit start-up "
        f"included), coded {net:.4f} bits/dim vs their -ELBO "
        f"{corpus_elbo:.4f} ({(net / corpus_elbo - 1) * 100:+.2f}%; "
        f"test -ELBO {elbo:.4f})")
    check(abs(net / corpus_elbo - 1) < 0.05,
          f"corpus: coded rate {net:.4f} is not within 5% of -ELBO "
          f"{corpus_elbo:.4f}")
    return codec, blob


@timed("serve")
def phase_serve(cfg, params, data):
    engine = fused_engine(params, cfg)
    blobs = []
    for lo in range(0, CHAIN, 2):
        req = data[lo:lo + 2]
        blob = engine.compress(req)
        check(same(engine.decompress(blob, 2, PIXELS), req),
              f"serve: one-shot request {lo // 2} is not lossless")
        blobs.append(blob)
    wire = engine.compress_stream(data, block_symbols=2)
    check(same(engine.decompress_stream(wire, PIXELS), data),
          "serve: the BBX2 stream is not lossless")
    log(f"serve: {len(blobs)} one-shot BBX1 requests "
        f"({sum(map(len, blobs))} bytes) and one BBX2 stream "
        f"({len(wire)} bytes), all lossless")
    return blobs[0]


def phase_backends(seen: dict) -> None:
    check(bool(seen), "backends: no coder op was dispatched")
    for op in sorted(seen):
        log(f"backend: {op} -> {', '.join(sorted(seen[op]))}")
    check(not any("interpret" in b for b in seen.values()),
          "backends: a hot op resolved to the Pallas interpreter")
    off = {op: sorted(b) for op, b in seen.items() if b != {"pallas"}}
    check(not off, f"backends: hot ops not on the pallas kernels: {off}")


@timed("cross-backend")
def phase_cross_backend(cfg, params, codec, data, corpus_blob, req_blob):
    """``corpus_blob`` and ``req_blob`` were encoded by the auto path,
    which ``phase_backends`` showed is ``pallas``."""
    from repro import shard_codec
    from repro.launch import compress as cli

    xla_corpus = cli.compress_corpus(codec, data, n_shards=1,
                                     block_symbols=CHAIN, seed=SEED,
                                     kernel_backend="xla")
    check(xla_corpus == corpus_blob,
          "cross-backend: corpus bytes differ between pallas and xla")
    for blob, dec in ((corpus_blob, "xla"), (xla_corpus, "pallas")):
        out = shard_codec.decompress_dataset(codec, blob, compile=True,
                                             kernel_backend=dec)
        check(same(out, data), f"cross-backend: corpus decode under "
              f"{dec} is not lossless")

    req = data[0:2]
    engines = {b: fused_engine(params, cfg, kernel_backend=b)
               for b in ("pallas", "xla")}
    xla_req = engines["xla"].compress(req)
    check(xla_req == req_blob,
          "cross-backend: fixed-point bytes differ between pallas and xla")
    for blob, dec in ((req_blob, "xla"), (xla_req, "pallas")):
        check(same(engines[dec].decompress(blob, 2, PIXELS), req),
              f"cross-backend: fixed-point decode under {dec} is not "
              "lossless")
    log("cross-backend: float corpus and fixed-point request bytes "
        "identical under pallas and xla; each decodes under the other")


@timed("golden")
def phase_golden():
    from tests.golden.make_golden import GOLDEN_DIR, build

    fixtures = build()
    for name in GOLDEN:
        _encode, decode, data = fixtures[name]
        with open(os.path.join(GOLDEN_DIR, f"{name}.bin"), "rb") as f:
            blob = f.read()
        check(same(decode(blob), data),
              f"golden: committed {name} does not decode on the chip")
    log(f"golden: {', '.join(GOLDEN)} decode losslessly")


@timed("four-chip")
def phase_four_chips(devices) -> None:
    import jax

    from repro import shard_codec
    from repro.launch import compress as cli
    from repro.models import vae
    from repro.serve import CodecEngine, ShardedCodecEngine

    t0 = time.perf_counter()

    def step(msg: str) -> None:
        log(f"four-chip: [{time.perf_counter() - t0:.1f} s] {msg}")

    cfg = vae.paper_config("bernoulli")
    params = vae.init(jax.random.PRNGKey(SEED), cfg)
    codec = vae.make_bb_codec(params, cfg)
    # One image per lane: the float path compiles its eager programs
    # and its Mosaic coder programs once per device, so each further
    # device costs about as much as the first.
    _imgs, data, _ = cli.load_corpus("vae-bernoulli", LANES, LANES)

    spread = shard_codec.shard_devices(4)
    check(len(set(spread)) == 4, f"four-chip: shards share devices "
          f"{spread}")
    kw = dict(n_shards=4, block_symbols=1, seed=SEED, with_info=True)
    blob4, info4 = cli.compress_corpus(codec, data, devices=spread, **kw)
    step(f"4-shard compress_dataset, shard stacks on "
         f"{info4['shard_devices']}")
    check(info4["shard_devices"] == spread,
          "four-chip: a shard's stack is not on its own device")
    blob1, info1 = cli.compress_corpus(codec, data,
                                       devices=[devices[0]] * 4, **kw)
    check(info1["shard_devices"] == [devices[0]] * 4,
          "four-chip: the one-device encode left device 0")
    check(blob4 == blob1, "four-chip: 4-device corpus bytes differ from "
          "the 1-device encode")
    step(f"compress_dataset bytes identical on 4 devices and on 1 "
         f"({len(blob4)} bytes)")
    # Decoded on one device: a decode on all four would compile every
    # decode program four times, and what spans devices is the encode.
    out = shard_codec.decompress_dataset(codec, blob4, compile=True,
                                         devices=[devices[0]])
    check(same(out, data), "four-chip: corpus decode is not lossless")
    step("the 4-device BBX3 corpus decodes losslessly")

    # The float VAE runs the mesh's shard_map'd coder programs (the
    # fused fixed-point codec has its own one-device jits).
    sharded = ShardedCodecEngine(lambda shape: codec, n_shards=4,
                                 seed=SEED)
    mesh_devices = list(sharded.mesh.devices.flat)
    check(len(set(mesh_devices)) == 4,
          f"four-chip: lane mesh is not 4 devices: {mesh_devices}")
    single = CodecEngine(lambda shape: codec, seed=SEED, compile=True)
    b4, b1 = sharded.compress(data), single.compress(data)
    check(b4 == b1, "four-chip: SPMD one-shot bytes differ from the "
          "1-device engine")
    step(f"ShardedCodecEngine one-shot bytes over {mesh_devices} "
         f"identical to the 1-device engine ({len(b4)} bytes)")
    check(same(sharded.decompress(b4, 1, PIXELS), data),
          "four-chip: SPMD one-shot decode is not lossless")
    step("the SPMD one-shot decode is lossless")


def run_one_chip() -> None:
    from repro.launch import compress as cli

    seen = record_backends()
    cfg, params, elbo = phase_train()
    imgs, data, _ = cli.load_corpus("vae-bernoulli", LANES * CHAIN, LANES)
    codec, corpus_blob = phase_corpus(cfg, params, elbo, imgs, data)
    req_blob = phase_serve(cfg, params, data)
    phase_backends(seen)
    phase_cross_backend(cfg, params, codec, data, corpus_blob, req_blob)
    phase_golden()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the BB-ANS main path once on a TPU and check it.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole main path on one chip; 4: only "
                         "the paths that span four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable_compile_cache()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on "
              f"{devices[0].platform!r}); this test runs on the chip "
              "only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device['kind']} x{device['count']} "
        f"({device['platform']}); compile cache {cache_dir}")

    timer = CompileTimer()
    try:
        if args.chips == 4:
            phase_four_chips(devices)
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"compile (set-up) seconds: {timer.seconds:.1f} (backend "
        f"compile {timer.backend_seconds:.1f} s over {timer.programs} "
        f"programs, {timer.cache_hits} persistent-cache hits)")
    log(f"wall seconds: {time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

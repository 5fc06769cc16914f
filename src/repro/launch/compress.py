"""Dataset compression launcher - the Table-1 reproduction CLI.

``python -m repro.launch.compress`` trains the paper's VAE (or the
hierarchical HVAE) on synthetic MNIST, then streams the full test set
through the lane-sharded BB-ANS pipeline (``repro.shard_codec``):
the lane axis splits into per-device shards, every shard encodes its
own independently-decodable BBX2 segment, and the segments gather
into one BBX3 corpus blob. It finishes with the paper's Table-1
comparison - achieved BB-ANS bits/dim against gzip, bz2, lzma and
(real or proxy) per-image PNG - plus a lossless full-corpus decode
check.

    PYTHONPATH=src python -m repro.launch.compress \
        --arch vae-bernoulli --images 512 --train-steps 400

``--arch vae-beta_binomial`` runs the paper's full-range (0..255)
Table-1 model; ``--arch hvae-small2`` the 2-level convolutional
Bit-Swap codec. ``--shards`` defaults to every local device (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise
the multi-device path on CPU - wire bytes are identical either way;
docs/SCALING.md). The benchmark-suite twin of this launcher is
``benchmarks/dataset_rate.py``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import shard_codec
from repro.data import baselines as baseline_lib
from repro.data import synthetic_mnist
from repro.launch import compile_cache
from repro.models import vae as vae_lib
from repro.optim import adamw

ARCHS = ("vae-bernoulli", "vae-beta_binomial", "hvae-small2")


def train_vae(cfg: vae_lib.VAEConfig, *, steps: int, seed: int = 0,
              n_train: int = 8000, batch: int = 128, lr: float = 1e-3):
    """Train the paper's VAE on synthetic MNIST; returns ``(params,
    test -ELBO in bits/dim)``."""
    binary = cfg.likelihood == "bernoulli"
    train_imgs, _ = synthetic_mnist.load("train", n_train, seed)
    if binary:
        train_imgs = synthetic_mnist.binarize(train_imgs, seed)
    test_imgs, _ = synthetic_mnist.load("test", 1024, seed)
    if binary:
        test_imgs = synthetic_mnist.binarize(test_imgs, seed + 1)
    params = vae_lib.init(jax.random.PRNGKey(seed), cfg)
    opt = adamw.AdamW(learning_rate=adamw.cosine_lr(lr, 100, steps))
    state = opt.init(params)

    @jax.jit
    def step(params, state, key, imgs):
        loss, grads = jax.value_and_grad(vae_lib.loss)(
            params, cfg, key, imgs)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed + 1)
    for _ in range(steps):
        idx = rng.integers(0, len(train_imgs), batch)
        key, sub = jax.random.split(key)
        params, state, _ = step(
            params, state, sub, jnp.asarray(train_imgs[idx], jnp.int32))
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    elbo = float(np.mean([float(vae_lib.elbo_bits_per_dim(
        params, cfg, k, jnp.asarray(test_imgs, jnp.int32)))
        for k in keys]))
    return params, elbo


def train_dataset_model(arch: str, *, steps: int, seed: int = 0,
                        n_train: int = 8000, batch: int = 128,
                        lr: float = 1e-3):
    """Train the model behind ``--arch``; returns
    ``(per-datapoint codec factory, binary?, elbo bits/dim)``.

    The factory takes no arguments for the dense VAEs (fixed 784-dim
    input) and builds the Bit-Swap codec at 28x28 for the HVAE.
    """
    if arch.startswith("vae-"):
        cfg = vae_lib.paper_config(arch.split("-", 1)[1])
        params, elbo = train_vae(cfg, steps=steps, seed=seed,
                                 n_train=n_train, batch=batch, lr=lr)
        return ((lambda: vae_lib.make_bb_codec(params, cfg)),
                cfg.likelihood == "bernoulli", elbo)

    if arch == "hvae-small2":
        from repro.configs import hvae_img
        from repro.data import images as img_data
        from repro.models import hvae as hvae_lib
        cfg = hvae_img.SMALL2
        binary = cfg.likelihood == "bernoulli"
        train_imgs = img_data.load("train", n_train // 2, seed,
                                   hw=(28, 28), binarized=binary)
        test_imgs = img_data.load("test", 256, seed + 1, hw=(28, 28),
                                  binarized=binary)
        params = hvae_lib.init(jax.random.PRNGKey(seed), cfg)
        opt = adamw.AdamW(learning_rate=adamw.cosine_lr(2e-3, 100, steps))
        state = opt.init(params)

        @jax.jit
        def hstep(params, state, key, imgs):
            loss, grads = jax.value_and_grad(hvae_lib.loss)(
                params, cfg, key, imgs)
            params, state = opt.update(grads, state, params)
            return params, state, loss

        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed + 1)
        for _ in range(steps):
            idx = rng.integers(0, len(train_imgs), 64)
            key, sub = jax.random.split(key)
            params, state, _ = hstep(
                params, state, sub, jnp.asarray(train_imgs[idx],
                                                jnp.int32))
        keys = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
        elbo = float(np.mean([float(hvae_lib.elbo_bits_per_dim(
            params, cfg, k, jnp.asarray(test_imgs, jnp.int32)))
            for k in keys]))
        return (lambda: hvae_lib.make_bitswap_codec(
            params, cfg, (28, 28))), binary, elbo

    raise ValueError(f"unknown --arch {arch!r}; choose from {ARCHS}")


def load_corpus(arch: str, n_images: int, lanes: int,
                seed: int = 123) -> tuple:
    """The benchmark corpus: ``(images uint8 [n, 784], data [n_chain,
    lanes, ...] as the codec expects, binary?)``."""
    binary = arch != "vae-beta_binomial"
    imgs, _ = synthetic_mnist.load("test", n_images, seed)
    if binary:
        imgs = synthetic_mnist.binarize(imgs, seed)
    if arch == "hvae-small2":
        data = jnp.asarray(imgs.reshape(-1, lanes, 28, 28), jnp.int32)
    else:
        data = jnp.asarray(imgs.reshape(-1, lanes, 784), jnp.int32)
    return imgs, data, binary


def compress_corpus(codec, data, *, n_shards: int, block_symbols: int,
                    seed: int, init_chunks: int = 32,
                    compile: bool = True, **kwargs):
    """``shard_codec.compress_dataset`` with the CLI's defaults
    (``kwargs`` such as ``devices`` or ``with_info`` pass through)."""
    return shard_codec.compress_dataset(
        codec, data, n_shards=n_shards, block_symbols=block_symbols,
        seed=seed, init_chunks=init_chunks, compile=compile, **kwargs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vae-bernoulli", choices=ARCHS)
    ap.add_argument("--images", type=int, default=512,
                    help="test images to compress (the 'full set')")
    ap.add_argument("--train-steps", type=int, default=400)
    ap.add_argument("--lanes", type=int, default=8,
                    help="total ANS lanes (must divide by --shards)")
    ap.add_argument("--shards", type=int, default=0,
                    help="lane shards / BBX3 segments (0 = one per "
                         "local device)")
    ap.add_argument("--block-symbols", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-compile", action="store_true",
                    help="skip codecs.compile (slow interpreted path)")
    ap.add_argument("--skip-decode", action="store_true",
                    help="skip the lossless full-decode verification")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()

    n_shards = args.shards or len(jax.devices())
    if args.lanes % n_shards:
        raise SystemExit(f"--lanes {args.lanes} must divide into "
                         f"{n_shards} shards")
    if args.images % args.lanes:
        raise SystemExit(f"--images {args.images} must be a multiple "
                         f"of --lanes {args.lanes}")
    print(f"devices={len(jax.devices())} shards={n_shards} "
          f"lanes={args.lanes} arch={args.arch}")

    t0 = time.time()
    make_codec, binary, elbo = train_dataset_model(
        args.arch, steps=args.train_steps, seed=args.seed)
    print(f"trained in {time.time() - t0:.0f}s; "
          f"test -ELBO = {elbo:.4f} bits/dim")

    imgs, data, _ = load_corpus(args.arch, args.images, args.lanes)
    codec = make_codec()
    t0 = time.time()
    blob = compress_corpus(codec, data, n_shards=n_shards,
                           block_symbols=args.block_symbols,
                           seed=args.seed, compile=not args.no_compile)
    t_enc = time.time() - t0
    bpd = len(blob) * 8 / imgs.size
    info = shard_codec.corpus_info(blob)
    print(f"encoded {args.images} images in {t_enc:.1f}s "
          f"({imgs.size / t_enc / 1e6:.2f} Mdim/s): "
          f"{len(blob)} wire bytes over {info['n_shards']} shards")

    if not args.skip_decode:
        t0 = time.time()
        out = shard_codec.decompress_dataset(
            codec, blob, compile=not args.no_compile)
        ok = bool(jnp.array_equal(out, data))
        print(f"decoded in {time.time() - t0:.1f}s; lossless={ok}")
        if not ok:
            raise SystemExit("decode mismatch - corrupt corpus")

    rates = baseline_lib.baseline_rates(imgs, binary, with_png=True)
    print("\nTable 1 (bits/dim, lower is better; "
          f"{args.images} synthetic-MNIST images"
          f"{', binarized' if binary else ''}):")
    rows = [("BB-ANS (sharded, wire)", bpd),
            ("-ELBO bound", elbo)]
    rows += sorted(rates.items(), key=lambda kv: kv[1])
    for name, rate in rows:
        marker = "  <- this work" if name.startswith("BB-ANS") else ""
        print(f"  {name:24s} {rate:.4f}{marker}")
    worse = [k for k in ("gzip", "bz2") if rates[k] <= bpd]
    if worse:
        raise SystemExit(f"BB-ANS did not beat {worse} - "
                         "train longer (--train-steps)")
    print(f"\nBB-ANS beats gzip by "
          f"{(1 - bpd / rates['gzip']) * 100:.1f}% and bz2 by "
          f"{(1 - bpd / rates['bz2']) * 100:.1f}%")


if __name__ == "__main__":
    main()

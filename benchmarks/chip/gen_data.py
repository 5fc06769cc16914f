"""Synthetic MNIST corpora made on the device from a seed.

A copy of the digit renderer of ``repro.data.synthetic_mnist`` (stroke
polylines, a random affine jitter, a soft distance-field raster,
intensity jitter and faint background noise), written in ``jax.numpy``
and drawn with ``jax.random`` so that a corpus of ten thousand images
is made on the chip in one jitted call per block of images instead of
some tens of seconds of host numpy. The distribution is the program's;
the random stream is this file's own, so a seed gives the same corpus
on every run of this benchmark.
"""

from __future__ import annotations

import functools

import numpy as np

H = W = 28
DIM = H * W


def _circle(cx, cy, rx, ry, n=14, a0=0.0, a1=2 * np.pi):
    t = np.linspace(a0, a1, n)
    return np.stack([cx + rx * np.cos(t), cy + ry * np.sin(t)], axis=-1)


def _digit_strokes():
    d = {}
    d[0] = [_circle(0.5, 0.5, 0.21, 0.32)]
    d[1] = [np.array([[0.36, 0.28], [0.54, 0.16], [0.54, 0.84]])]
    d[2] = [np.concatenate([
        _circle(0.5, 0.32, 0.2, 0.17, 7, -np.pi, 0.0),
        np.array([[0.68, 0.38], [0.3, 0.84], [0.72, 0.84]])])]
    d[3] = [np.concatenate([
        _circle(0.47, 0.32, 0.2, 0.16, 7, -np.pi * 0.8, np.pi * 0.5),
        _circle(0.47, 0.67, 0.22, 0.18, 7, -np.pi * 0.5, np.pi * 0.82)])]
    d[4] = [np.array([[0.58, 0.14], [0.27, 0.6], [0.76, 0.6]]),
            np.array([[0.6, 0.34], [0.6, 0.86]])]
    d[5] = [np.concatenate([
        np.array([[0.7, 0.16], [0.33, 0.16], [0.31, 0.48]]),
        _circle(0.48, 0.65, 0.22, 0.19, 8, -np.pi * 0.45, np.pi * 0.75)])]
    d[6] = [np.concatenate([
        np.array([[0.64, 0.14], [0.42, 0.36]]),
        _circle(0.47, 0.65, 0.18, 0.2, 10, np.pi * 0.75,
                np.pi * 0.75 + 2 * np.pi)])]
    d[7] = [np.array([[0.3, 0.16], [0.72, 0.16], [0.44, 0.86]])]
    d[8] = [_circle(0.5, 0.33, 0.16, 0.15),
            _circle(0.5, 0.67, 0.2, 0.17)]
    d[9] = [_circle(0.52, 0.35, 0.17, 0.17),
            np.array([[0.69, 0.38], [0.6, 0.86]])]
    return [d[i] for i in range(10)]


def _segments():
    """All strokes as [10, S, 2, 2] segments and a [10, S] mask."""
    per_digit = []
    for polys in _digit_strokes():
        per_digit.append(np.array([np.stack([p[i], p[i + 1]])
                                   for p in polys
                                   for i in range(len(p) - 1)]))
    s_max = max(len(s) for s in per_digit)
    segs = np.zeros((10, s_max, 2, 2), np.float32)
    mask = np.zeros((10, s_max), bool)
    for i, s in enumerate(per_digit):
        segs[i, :len(s)] = s
        mask[i, :len(s)] = True
    return segs, mask


def seed32(seed: int, salt: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole ``seed``."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), salt])
    return int(ss.generate_state(1)[0]) & 0x7FFFFFFF


@functools.lru_cache(maxsize=None)
def _render_fn(n: int, binarized: bool):
    import jax
    import jax.numpy as jnp

    segs_np, mask_np = _segments()
    ys, xs = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    grid_np = np.stack([(xs + 0.5) / W, (ys + 0.5) / H],
                       -1).reshape(-1, 2).astype(np.float32)

    @jax.jit
    def render(key):
        segs, mask, grid = (jnp.asarray(segs_np), jnp.asarray(mask_np),
                            jnp.asarray(grid_np))
        k = jax.random.split(key, 9)
        labels = jax.random.randint(k[0], (n,), 0, 10)
        u = lambda kk, shape, lo, hi: jax.random.uniform(
            kk, shape, jnp.float32, lo, hi)
        ang = u(k[1], (n,), -0.18, 0.18)
        scale = u(k[2], (n, 1), 0.85, 1.12)
        shear = u(k[3], (n,), -0.12, 0.12)
        tx = u(k[4], (n, 2), -0.07, 0.07)
        ca, sa = jnp.cos(ang), jnp.sin(ang)
        rot = jnp.stack([jnp.stack([ca, -sa], -1),
                         jnp.stack([sa, ca], -1)], -2)
        shm = jnp.tile(jnp.eye(2, dtype=jnp.float32), (n, 1, 1))
        shm = shm.at[:, 0, 1].set(shear)
        hi = jax.lax.Precision.HIGHEST
        amat = jnp.einsum("nij,njk->nik", rot, shm,
                          precision=hi) / scale[..., None]
        coords = jnp.einsum("nij,pj->npi", amat, grid - 0.5,
                            precision=hi) + 0.5 + tx[:, None]
        sg, mk = segs[labels], mask[labels]
        a = sg[:, :, 0][:, None]
        b = sg[:, :, 1][:, None]
        p = coords[:, :, None]
        ab = b - a
        t = jnp.clip(((p - a) * ab).sum(-1)
                     / ((ab * ab).sum(-1) + 1e-9), 0.0, 1.0)
        proj = a + t[..., None] * ab
        dist = jnp.sqrt(((p - proj) ** 2).sum(-1))
        dist = jnp.where(mk[:, None], dist, jnp.inf).min(-1)
        width = u(k[5], (n, 1), 0.032, 0.05)
        peak = u(k[6], (n, 1), 0.75, 1.0)
        img = jnp.clip(jnp.exp(-0.5 * (dist / width) ** 2) * peak * 255.0,
                       0, 255)
        img = img + u(k[7], img.shape, 0.0, 6.0)
        img = jnp.floor(jnp.clip(img, 0, 255))
        if binarized:
            img = (jax.random.uniform(k[8], img.shape) < img / 255.0)
        return img.astype(jnp.uint8)

    return render


def corpus(n: int, seed: int, *, binarized: bool, block: int = 1024,
           salt: int = 0xC0DE):
    """``n`` images as a device array uint8[n, 784], made from ``seed``
    (and ``salt``, which keeps a training set apart from any corpus)
    in blocks of ``block`` images (one compiled program)."""
    import jax
    import jax.numpy as jnp

    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} images do not split into blocks of {block}")
    render = _render_fn(block, binarized)
    key = jax.random.PRNGKey(seed32(seed, salt))
    keys = jax.random.split(key, n // block)
    return jnp.concatenate([render(k) for k in keys])

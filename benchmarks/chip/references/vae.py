"""Plain reference of the paper's VAE (Townsend et al., ICLR 2019, s3).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``; it
imports nothing of the program. Two departures from the paper, both
the repo's model choices and kept here so that the reference computes
the same function: the log-variance is clipped to [-10, 10], and the
beta-binomial's parameters are ``softplus(.) + 1e-4``. Latent bucket
centres are the prior's quantiles ``Phi^-1((i + 1/2) / 2^lat_bits)``,
taken in float64 from ``statistics.NormalDist``.

``dot_inputs`` names the dtype that matmul inputs are rounded to
before a float32 product: ``None`` for the reference itself, a lower
one (``float8_e4m3fn``) for the control that stands in the program's
place.

The weights of a configuration are made here too, by ``train``: a
textbook fit from the configuration's seed (He-normal initial weights,
Adam with bias correction at a constant rate, the reparameterized
ELBO), so that the program under test and this reference are both
given weights that neither made.
"""

from __future__ import annotations

import functools
import statistics
from typing import Dict, Optional, Tuple

import numpy as np

Params = Dict[str, Dict[str, np.ndarray]]


def _dot(x, w, dot_inputs: Optional[str]):
    import jax
    import jax.numpy as jnp
    if dot_inputs is not None:
        x = x.astype(dot_inputs).astype(jnp.float32)
        w = w.astype(dot_inputs).astype(jnp.float32)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _dense(p, x, dot_inputs):
    return _dot(x, p["w"], dot_inputs) + p["b"]


def encode(params: Params, cfg: dict, s, dot_inputs=None):
    """Pixels int[lanes, 784] -> posterior (mu, sigma), float32."""
    import jax
    import jax.numpy as jnp
    scale = 1.0 if cfg["likelihood"] == "bernoulli" else 255.0
    x = s.astype(jnp.float32) / scale
    h = jax.nn.relu(_dense(params["enc_h"], x, dot_inputs))
    mu = _dense(params["enc_mu"], h, dot_inputs)
    logvar = jnp.clip(_dense(params["enc_logvar"], h, dot_inputs),
                      -10.0, 10.0)
    return mu, jnp.exp(0.5 * logvar)


def decode(params: Params, cfg: dict, y, dot_inputs=None):
    """Latents float[lanes, latent] -> Bernoulli logits [lanes, 784] or
    beta-binomial (alpha, beta) [lanes, 784, 2]."""
    import jax
    h = jax.nn.relu(_dense(params["dec_h"], y, dot_inputs))
    out = _dense(params["dec_out"], h, dot_inputs)
    if cfg["likelihood"] == "bernoulli":
        return out
    return jax.nn.softplus(out.reshape(out.shape[0], cfg["input_dim"],
                                       2)) + 1e-4


@functools.lru_cache(maxsize=None)
def centres(lat_bits: int) -> np.ndarray:
    k = 1 << lat_bits
    nd = statistics.NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / k) for i in range(k)])


def bucket_of(y: np.ndarray, lat_bits: int) -> np.ndarray:
    """The bucket index whose centre is nearest each latent value."""
    c = centres(lat_bits)
    i = np.clip(np.searchsorted(c, y), 1, len(c) - 1)
    return np.where(np.abs(y - c[i - 1]) <= np.abs(y - c[i]), i - 1, i)


def _log_pmf(cfg: dict, obs, s):
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import gammaln
    x = s.astype(jnp.float32)
    if cfg["likelihood"] == "bernoulli":
        return -(jax.nn.softplus(-obs) * x + jax.nn.softplus(obs) * (1 - x))
    n = 255.0
    a, b = obs[..., 0], obs[..., 1]
    lbeta = lambda p, q: gammaln(p) + gammaln(q) - gammaln(p + q)
    return (gammaln(n + 1) - gammaln(x + 1) - gammaln(n - x + 1)
            + lbeta(x + a, n - x + b) - lbeta(a, b))


def _neg_elbo_nats(params: Params, cfg: dict, s, key):
    """-ELBO of each image of ``s`` in nats, from one posterior draw."""
    import jax
    import jax.numpy as jnp
    mu, sigma = encode(params, cfg, s)
    y = mu + sigma * jax.random.normal(key, mu.shape)
    recon = _log_pmf(cfg, decode(params, cfg, y), s).sum(-1)
    kl = 0.5 * jnp.sum(mu ** 2 + sigma ** 2 - 1.0 - 2.0 * jnp.log(sigma),
                       axis=-1)
    return kl - recon


def init(cfg: dict, seed: int) -> Params:
    """He-normal weights and zero biases, from ``seed``."""
    import jax
    import jax.numpy as jnp
    out = 1 if cfg["likelihood"] == "bernoulli" else 2
    shapes = {"enc_h": (cfg["input_dim"], cfg["hidden"]),
              "enc_mu": (cfg["hidden"], cfg["latent"]),
              "enc_logvar": (cfg["hidden"], cfg["latent"]),
              "dec_h": (cfg["latent"], cfg["hidden"]),
              "dec_out": (cfg["hidden"], cfg["input_dim"] * out)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: {"w": jax.random.normal(k, shape, jnp.float32)
                   * np.sqrt(2.0 / shape[0]),
                   "b": jnp.zeros((shape[1],), jnp.float32)}
            for k, (name, shape) in zip(keys, shapes.items())}


def train(cfg: dict, imgs, *, steps: int, seed: int, batch: int,
          lr: float, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8) -> Tuple[Params, float]:
    """Fit the VAE to ``imgs`` (uint8[n, 784], on the device) in one
    jitted call: ``steps`` Adam steps on batches drawn from ``seed``.
    Returns the weights and the mean -ELBO of the last 100 batches in
    bits per dimension."""
    import jax
    import jax.numpy as jnp

    def loss(p, s, key):
        return jnp.mean(_neg_elbo_nats(p, cfg, s, key))

    @jax.jit
    def fit(imgs, key):
        k_init, k_steps = jax.random.split(key)
        params = init(cfg, int(seed))
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

        def step(carry, k):
            p, m, v, t = carry
            k_batch, k_draw = jax.random.split(k)
            idx = jax.random.randint(k_batch, (batch,), 0, imgs.shape[0])
            value, g = jax.value_and_grad(loss)(p, imgs[idx], k_draw)
            t = t + 1.0
            m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b,
                                       m, g)
            v = jax.tree_util.tree_map(
                lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree_util.tree_map(
                lambda w, a, b: w - lr * (a / (1 - b1 ** t))
                / (jnp.sqrt(b / (1 - b2 ** t)) + eps), p, m, v)
            return (p, m, v, t), value

        (params, _, _, _), losses = jax.lax.scan(
            step, (params, zeros, zeros, jnp.float32(0.0)),
            jax.random.split(k_steps, steps))
        return params, jnp.mean(losses[-100:])

    params, nats = fit(imgs, jax.random.PRNGKey(seed + 1))
    return params, float(nats) / cfg["input_dim"] / np.log(2.0)


def neg_elbo_bits_per_dim(params: Params, cfg: dict, imgs, seed: int,
                          samples: int = 4, block: int = 1024) -> float:
    """-ELBO of ``imgs`` (uint8[n, 784]) in bits per dimension, the mean
    over ``samples`` posterior draws, computed in blocks of images."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block_nats(p, s, key):
        return jnp.sum(_neg_elbo_nats(p, cfg, s, key))

    total = 0.0
    key = jax.random.PRNGKey(seed)
    for j in range(samples):
        for lo in range(0, imgs.shape[0], block):
            key, sub = jax.random.split(key)
            total += float(block_nats(params, imgs[lo:lo + block], sub))
    n_dims = imgs.shape[0] * cfg["input_dim"] * samples
    return total / n_dims / np.log(2.0)


def compare_forward(params: Params, cfg: dict, calls: Dict[str, list],
                    ) -> Tuple[float, Dict[str, float]]:
    """Widest gap of the recorded forward outputs from the reference.

    ``calls["encode"]`` holds ``(pixels, mu, sigma)`` and
    ``calls["decode"]`` ``(latents, params)`` as the program's forward
    returned them. Each output is compared as the reference computes
    it on the same inputs (the latents by their bucket index, with the
    reference's own centres): the largest absolute difference, over
    the root mean square of the reference's values. Posterior scales
    and beta-binomial parameters are compared as logs.

    Returns ``(widest gap, gap of each output)``.
    """
    import jax.numpy as jnp
    gaps: Dict[str, list] = {}

    def add(name, prog, ref):
        prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
        rms = float(np.sqrt(np.mean(ref ** 2))) or 1.0
        gaps.setdefault(name, []).append(
            float(np.max(np.abs(prog - ref))) / rms)

    for s, mu, sigma in calls.get("encode", []):
        r_mu, r_sigma = encode(params, cfg, jnp.asarray(s))
        add("posterior_mu", mu, r_mu)
        add("posterior_log_sigma", np.log(sigma), np.log(np.asarray(r_sigma)))
    for y, obs in calls.get("decode", []):
        idx = bucket_of(np.asarray(y, np.float64), cfg["lat_bits"])
        y_ref = centres(cfg["lat_bits"])[idx].astype(np.float32)
        r = np.asarray(decode(params, cfg, jnp.asarray(y_ref)))
        if cfg["likelihood"] == "bernoulli":
            add("bernoulli_logits", obs, r)
        else:
            add("betabinomial_log_params", np.log(obs), np.log(r))
    if not gaps:
        raise ValueError("no forward call was recorded")
    per = {k: max(v) for k, v in gaps.items()}
    return max(per.values()), per

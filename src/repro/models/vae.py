"""The paper's VAE (section 3.1-3.2) and its BB-ANS codec hooks.

Fully-connected VAE with ReLU activations, diagonal-Gaussian posterior and
standard-normal prior. Two likelihood heads, as in the paper:

  * ``bernoulli``     - binarized MNIST: 1 logit/pixel, hidden 100, latent 40.
  * ``beta_binomial`` - full MNIST (0..255): 2 params/pixel, hidden 200,
    latent 50.

Pure-functional: ``init``/``encode``/``decode``/``elbo`` plus
``make_bb_codec``, which returns the model as a composable
``codecs.BBANS`` combinator (lane = batch element) for use with
``codecs.compress``/``decompress`` or the ``repro.stream`` BBX2 path;
``compiled=True`` lowers it into one fused jit program
(``codecs.compile``) with identical wire bytes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import codecs, spans
from repro.codecs import quantize
from repro.core import ans, discretize
from repro.core.distributions import Bernoulli, BetaBinomial

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    input_dim: int = 784
    hidden: int = 100
    latent: int = 40
    likelihood: str = "bernoulli"  # or "beta_binomial"
    # Coding parameters (paper section 2.5.1: 16 bits/latent dim suffice;
    # 10-bit buckets within 16-bit coder precision keep the fixed-point
    # prior-smearing term eps = 2^(lat_bits-precision) below 2%).
    lat_bits: int = 10
    precision: int = 16
    obs_precision: int = 16

    @property
    def obs_symbols(self) -> int:
        return 2 if self.likelihood == "bernoulli" else 256


def paper_config(likelihood: str) -> VAEConfig:
    """The exact two configurations used in the paper's experiments."""
    if likelihood == "bernoulli":
        return VAEConfig(hidden=100, latent=40, likelihood="bernoulli")
    elif likelihood == "beta_binomial":
        return VAEConfig(hidden=200, latent=50, likelihood="beta_binomial")
    raise ValueError(likelihood)


def _dense_init(key, n_in, n_out):
    k1, _ = jax.random.split(key)
    w = jax.random.normal(k1, (n_in, n_out)) * jnp.sqrt(2.0 / n_in)
    return {"w": w.astype(jnp.float32),
            "b": jnp.zeros((n_out,), jnp.float32)}


def init(key: jax.Array, cfg: VAEConfig) -> Params:
    keys = jax.random.split(key, 5)
    out_mult = 1 if cfg.likelihood == "bernoulli" else 2
    return {
        "enc_h": _dense_init(keys[0], cfg.input_dim, cfg.hidden),
        "enc_mu": _dense_init(keys[1], cfg.hidden, cfg.latent),
        "enc_logvar": _dense_init(keys[2], cfg.hidden, cfg.latent),
        "dec_h": _dense_init(keys[3], cfg.latent, cfg.hidden),
        "dec_out": _dense_init(keys[4], cfg.hidden,
                               cfg.input_dim * out_mult),
    }


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _norm_input(cfg: VAEConfig, s: jnp.ndarray) -> jnp.ndarray:
    scale = 1.0 if cfg.likelihood == "bernoulli" else 255.0
    return s.astype(jnp.float32) / scale


def encode(params: Params, cfg: VAEConfig,
           s: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """s int[lanes, input_dim] -> (mu, sigma) each float[lanes, latent]."""
    h = jax.nn.relu(_dense(params["enc_h"], _norm_input(cfg, s)))
    mu = _dense(params["enc_mu"], h)
    logvar = jnp.clip(_dense(params["enc_logvar"], h), -10.0, 10.0)
    return mu, jnp.exp(0.5 * logvar)


def decode(params: Params, cfg: VAEConfig, y: jnp.ndarray) -> jnp.ndarray:
    """y float[lanes, latent] -> obs params.

    bernoulli: logits float[lanes, input_dim];
    beta_binomial: (alpha, beta) float[lanes, input_dim, 2], positive.
    """
    h = jax.nn.relu(_dense(params["dec_h"], y))
    out = _dense(params["dec_out"], h)
    if cfg.likelihood == "bernoulli":
        return out
    ab = out.reshape(out.shape[0], cfg.input_dim, 2)
    return jax.nn.softplus(ab) + 1e-4


def obs_log_prob(cfg: VAEConfig, obs_params: jnp.ndarray,
                 s: jnp.ndarray) -> jnp.ndarray:
    """Sum log p(s|y) over pixels -> float[lanes]."""
    if cfg.likelihood == "bernoulli":
        dist = Bernoulli(obs_params.reshape(-1))
        lp = dist.log_prob(s.reshape(-1).astype(jnp.float32))
        return lp.reshape(s.shape).sum(-1)
    alpha, beta = obs_params[..., 0], obs_params[..., 1]
    from repro.core.distributions import beta_binomial_log_pmf
    lp = beta_binomial_log_pmf(s.astype(jnp.float32), 255, alpha, beta)
    return lp.sum(-1)


def elbo(params: Params, cfg: VAEConfig, key: jax.Array,
         s: jnp.ndarray) -> jnp.ndarray:
    """Per-example ELBO in nats, float[lanes]. -ELBO == expected BB-ANS
    message length (paper eq. 1-2)."""
    mu, sigma = encode(params, cfg, s)
    eps = jax.random.normal(key, mu.shape)
    y = mu + sigma * eps
    obs = decode(params, cfg, y)
    recon = obs_log_prob(cfg, obs, s)
    kl = 0.5 * jnp.sum(mu ** 2 + sigma ** 2 - 1.0
                       - 2.0 * jnp.log(sigma), axis=-1)
    return recon - kl


def elbo_bits_per_dim(params: Params, cfg: VAEConfig, key: jax.Array,
                      s: jnp.ndarray) -> jnp.ndarray:
    return -jnp.mean(elbo(params, cfg, key, s)) / (
        cfg.input_dim * jnp.log(2.0))


def loss(params: Params, cfg: VAEConfig, key: jax.Array,
         s: jnp.ndarray) -> jnp.ndarray:
    return -jnp.mean(elbo(params, cfg, key, s))


# ---------------------------------------------------------------------------
# BB-ANS codec (paper Table 1, App. C) via the composable codecs API
# ---------------------------------------------------------------------------

def quantize_model(params: Params, cfg: VAEConfig,
                   qcfg: quantize.QuantConfig = quantize.QuantConfig()
                   ) -> Params:
    """Quantize the VAE's dense layers to the fixed-point format
    (``codecs.quantize``): int32 weights/biases, ready for the
    integer-exact forward passes below."""
    del cfg
    return quantize.quantize_params(params, qcfg)


def encode_q(qparams: Params, cfg: VAEConfig, qcfg: quantize.QuantConfig,
             s: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-point twin of ``encode``: s int[lanes, input_dim] ->
    deterministic float32 (mu, sigma). Integer matmuls, LUT sigma."""
    x_q = quantize.quantize_input(s, qcfg)
    h = quantize.relu_q(quantize.dense_q(qparams["enc_h"], x_q, qcfg))
    mu_q = quantize.dense_q(qparams["enc_mu"], h, qcfg)
    lv_q = quantize.dense_q(qparams["enc_logvar"], h, qcfg)
    return quantize.gaussian_head(mu_q, lv_q, qcfg)


def decode_freq1_q(qparams: Params, cfg: VAEConfig,
                   qcfg: quantize.QuantConfig,
                   idx: jnp.ndarray) -> jnp.ndarray:
    """Fixed-point twin of ``decode`` (bernoulli): bucket indices
    int[lanes, latent] -> uint32[lanes, input_dim] fixed-point freq of
    pixel = 1 (LUT on the quantized logits)."""
    y_q = quantize.latent_centres_q(idx, cfg.lat_bits, qcfg)
    h = quantize.relu_q(quantize.dense_q(qparams["dec_h"], y_q, qcfg))
    logit_q = quantize.dense_q(qparams["dec_out"], h, qcfg)
    return quantize.bernoulli_head(logit_q, cfg.obs_precision, qcfg)


def make_bb_codec_q(params: Params, cfg: VAEConfig, *,
                    qcfg: quantize.QuantConfig = quantize.QuantConfig(),
                    compiled: bool = False) -> codecs.Codec:
    """The *quantized* VAE as a BBANS combinator (HiLLoC-style).

    Model inference runs in fixed point (``codecs.quantize``), so the
    posterior/likelihood children are ``FixedPointFn`` markers:
    interpreted, the codec behaves like any other combinator tree;
    ``compiled=True`` fuses the whole per-datapoint schedule - network
    forward, bucketize, ANS renorm - into ONE jit program per
    direction (and a ``Chained`` wrapper into one ``lax.scan``
    program for the whole chain). Wire bytes are identical between the
    two paths; they differ from the float model's bytes (a quantized
    net is a coarser model - rate cost is the quantization error).

    Only the bernoulli likelihood is supported in fixed point (the
    beta-binomial table build needs float special functions that have
    no LUT form over a 2-parameter context).
    """
    if cfg.likelihood != "bernoulli":
        raise ValueError(
            "make_bb_codec_q: fixed-point inference supports the "
            f"bernoulli likelihood only (got {cfg.likelihood!r})")
    qp = quantize_model(params, cfg, qcfg)

    posterior = quantize.FixedPointFn(
        lambda s: encode_q(qp, cfg, qcfg, s),
        "gaussian", cfg.latent, cfg.lat_bits, cfg.precision)
    likelihood = quantize.FixedPointFn(
        lambda idx: decode_freq1_q(qp, cfg, qcfg, idx),
        "bernoulli", cfg.input_dim, 0, cfg.obs_precision)
    prior = codecs.Repeat(
        lambda d: codecs.Uniform(cfg.lat_bits, cfg.precision), cfg.latent)
    bb = codecs.BBANS(prior=prior, likelihood=likelihood,
                      posterior=posterior)
    return codecs.compile(bb) if compiled else bb


def make_bb_codec(params: Params, cfg: VAEConfig, *,
                  compiled: bool = False) -> codecs.Codec:
    """The VAE as a composable ``codecs.BBANS`` combinator.

    The latent symbol ``y`` is carried as *bucket indices* int32[lanes,
    latent] under the max-entropy discretization of the prior; the network
    consumes bucket centres. Pixels are coded conditionally-independently
    given y, so intra-datapoint order is free; ``Repeat`` pushes in
    reverse so pops stream in natural order.

    ``compiled=True`` runs the codec through ``codecs.compile``: the
    whole per-datapoint encode/decode (posterior pop, likelihood push,
    prior push, networks included) becomes one fused jit program with
    kernel-backed multi-symbol coding - byte-identical wire, several
    times faster (benchmarks/codec_compile.py). For chained data,
    compiling the whole chain is better still:
    ``codecs.compile(codecs.Chained(make_bb_codec(p, cfg), n))``.

    Use directly with the container:
        blob = codecs.compress(codecs.Chained(make_bb_codec(p, cfg), n),
                               data, lanes=lanes, seed=0)
    """
    @spans.spanned(spans.FORWARD)
    def posterior(s):
        mu, sigma = encode(params, cfg, s)
        return codecs.Repeat(
            lambda d: codecs.DiscretizedGaussian(
                mu[:, d], sigma[:, d], cfg.lat_bits, cfg.precision),
            cfg.latent)

    @spans.spanned(spans.FORWARD)
    def likelihood(idx):
        y = discretize.bucket_centre(idx, cfg.lat_bits)
        obs_params = decode(params, cfg, y)
        if cfg.likelihood == "bernoulli":
            return codecs.Repeat(
                lambda d: Bernoulli(obs_params[:, d], cfg.obs_precision),
                cfg.input_dim)
        return codecs.Repeat(
            lambda d: BetaBinomial(obs_params[:, d, 0], obs_params[:, d, 1],
                                   255, cfg.obs_precision),
            cfg.input_dim)

    prior = codecs.Repeat(
        lambda d: codecs.Uniform(cfg.lat_bits, cfg.precision), cfg.latent)
    bb = codecs.BBANS(prior=prior, likelihood=likelihood,
                      posterior=posterior)
    return codecs.compile(bb) if compiled else bb

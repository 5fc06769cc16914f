"""The system under test for a VAE configuration: the paper's VAE coded
by the repo's BB-ANS codec on the Table-1 path.

The weights are given to it (``weights.py`` makes them with the
benchmark's own trainer), so the program under test computes with a
model that it did not make.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import numpy as np


class System:
    """One configuration's model, codec and its two corpus passes."""

    def __init__(self, config: dict):
        from repro.models import vae
        self.config = config
        self.cfg = vae.VAEConfig(
            input_dim=config["input_dim"], hidden=config["hidden"],
            latent=config["latent"], likelihood=config["likelihood"],
            lat_bits=config["lat_bits"], precision=config["precision"],
            obs_precision=config["obs_precision"])
        self.binarized = config["data"] == "binarized"
        self.params = None

    def load(self, host: Dict[str, Dict[str, np.ndarray]]) -> None:
        """Put the weights on the device, in the program's layout."""
        import jax.numpy as jnp
        self.params = {layer: {k: jnp.asarray(v) for k, v in d.items()}
                       for layer, d in host.items()}

    # -- the path under test ---------------------------------------------
    def codec(self):
        from repro.models import vae
        return vae.make_bb_codec(self.params, self.cfg)

    def encode(self, codec, data, traffic: dict, seed: int,
               with_info: bool = False):
        """One Table-1 encode pass: the blob, in host memory."""
        from repro.launch import compress
        return compress.compress_corpus(
            codec, data, n_shards=traffic["shards"],
            block_symbols=traffic["block_symbols"], seed=seed,
            init_chunks=traffic["init_chunks"], compile=traffic["compile"],
            with_info=with_info)

    def decode(self, codec, blob: bytes, traffic: dict) -> np.ndarray:
        """One decode pass: the images, in host memory."""
        from repro import shard_codec
        return np.asarray(shard_codec.decompress_dataset(
            codec, blob, compile=traffic["compile"]))

    # -- observing and replacing the model's forward ----------------------
    @contextlib.contextmanager
    def recorded_forward(self):
        """Record, in host memory, every concrete call of the model's
        forward that the coder makes: ``{"encode": [(pixels, mu,
        sigma)], "decode": [(latents, obs params)]}``. The program's
        own functions compute every value; they are only observed."""
        from repro.models import vae
        calls: Dict[str, list] = {"encode": [], "decode": []}
        enc, dec = vae.encode, vae.decode

        def concrete(*xs) -> Optional[list]:
            try:
                return [np.asarray(x) for x in xs]
            except Exception:     # traced while a program is lowered
                return None

        def encode(params, cfg, s):
            mu, sigma = enc(params, cfg, s)
            got = concrete(s, mu, sigma)
            if got is not None:
                calls["encode"].append(tuple(got))
            return mu, sigma

        def decode(params, cfg, y):
            obs = dec(params, cfg, y)
            got = concrete(y, obs)
            if got is not None:
                calls["decode"].append(tuple(got))
            return obs

        vae.encode, vae.decode = encode, decode
        try:
            yield calls
        finally:
            vae.encode, vae.decode = enc, dec

    @contextlib.contextmanager
    def replaced_forward(self, encode_fn, decode_fn):
        """Put another forward in the program's place:
        ``encode_fn(s) -> (mu, sigma)``, ``decode_fn(y) -> obs``."""
        from repro.models import vae
        enc, dec = vae.encode, vae.decode
        vae.encode = lambda params, cfg, s: encode_fn(s)
        vae.decode = lambda params, cfg, y: decode_fn(y)
        try:
            yield
        finally:
            vae.encode, vae.decode = enc, dec

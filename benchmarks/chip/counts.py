"""Work counted from the algorithm and the shapes, never from an
implementation's buffers: model FLOPs per image, and the bytes the
BB-ANS coding of one image must move.

One image coded by BB-ANS (Townsend et al. 2019, s2) runs both of the
VAE's networks once, in either direction: the encoder for the
posterior and the decoder for the likelihood. Its symbols are the
``latent`` posterior symbols, the ``input_dim`` pixels and the
``latent`` prior symbols. A push must read the symbol's start and
frequency (two table entries); a pop must search the cumulative table
of ``K`` symbols, ``ceil(log2 K) + 1`` entries; each symbol is read or
written as 4 bytes; the coder writes (or reads) its 16-bit chunks,
whose number is fixed by the measured wire bytes.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

ENTRY_BYTES = 4      # a uint32 table entry
SYMBOL_BYTES = 4     # an int32 symbol


def vae_macs(config: dict) -> int:
    """Multiply-accumulates of one forward of both networks."""
    d, h, z = config["input_dim"], config["hidden"], config["latent"]
    out = d * (1 if config["likelihood"] == "bernoulli" else 2)
    encoder = d * h + h * z * 2          # hidden, then mu and log-variance
    decoder = z * h + h * out
    return encoder + decoder


def vae_flops_per_image(config: dict) -> int:
    """Model FLOPs of coding one image, either direction."""
    return 2 * vae_macs(config)


def _search_entries(k: int) -> int:
    return math.ceil(math.log2(k)) + 1


def coder_bytes_per_image(config: dict, direction: str,
                          wire_bytes_per_image: float) -> float:
    """Least bytes the coding of one image moves, by ``direction``
    (``encode`` or ``decode``)."""
    z, d = config["latent"], config["input_dim"]
    k_lat = 1 << config["lat_bits"]
    k_obs = 2 if config["likelihood"] == "bernoulli" else 256
    push = 2
    if direction == "encode":
        # pop z from the posterior, push the pixels, push z under the prior
        entries = z * _search_entries(k_lat) + d * push + z * push
    elif direction == "decode":
        # pop z under the prior, pop the pixels, push z to the posterior
        entries = z * _search_entries(k_lat) + d * _search_entries(k_obs) \
            + z * push
    else:
        raise ValueError(direction)
    symbols = 2 * z + d
    return (symbols * SYMBOL_BYTES + entries * ENTRY_BYTES
            + wire_bytes_per_image)


def roofline(bytes_moved: float, flops: float, seconds: float,
             peaks: Dict[str, float]) -> Dict[str, float]:
    """Share (%) of the least time over the time taken, and which bound
    sets the least time (``hbm`` or ``bf16``)."""
    t_mem = bytes_moved / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops"]
    least = max(t_mem, t_flop)
    return {"percent": 100.0 * least / seconds,
            "bound": "hbm" if t_mem >= t_flop else "bf16"}


def mb_per_s(bytes_per_pass: float, seconds: Sequence[float]
             ) -> Optional[float]:
    """Raw input MB/s (10^6 bytes) over the summed time of whole passes,
    each of ``bytes_per_pass``; ``None`` with no pass."""
    if not seconds:
        return None
    return len(seconds) * bytes_per_pass / 1e6 / sum(seconds)

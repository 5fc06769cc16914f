"""The weights of a configuration, made by the benchmark and not by
the program under test.

The configuration's plain reference trains them (``train`` in
``references/<reference>.py``) from the configuration's ``train``
settings, on a synthetic-MNIST training set that ``gen_data`` makes
on the device from the training seed. The first run in a checkout
trains and keeps the weights in ``<cache>/<config>-<key>.npz``; later
runs load that file, as a service loads its model. The key covers the
keys that shape the weights and the source of the trainer and the
data generator, so a change to either trains anew.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import time
from typing import Callable, Dict, Tuple

import numpy as np

from benchmarks.chip import gen_data, registry

#: The configuration keys that change the trained weights. The coder's
#: precisions do not: a control run at a lower precision loads the
#: same weights.
WEIGHT_KEYS = ("input_dim", "hidden", "latent", "likelihood", "data",
               "train", "reference")
#: Salt of the training set's random stream, apart from any corpus's.
TRAIN_SALT = 0x7EA1

Host = Dict[str, Dict[str, np.ndarray]]


def path(config: dict, cache: str) -> str:
    h = hashlib.sha256(json.dumps(
        {k: config[k] for k in WEIGHT_KEYS}, sort_keys=True).encode())
    for src in (os.path.join(registry.HERE, "references",
                             f"{config['reference']}.py"),
                os.path.join(registry.HERE, "gen_data.py")):
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(cache, f"{config['name']}-{h.hexdigest()[:16]}.npz")


def load_or_train(config: dict, reference, cache: str,
                  log: Callable[[str], None]) -> Tuple[Host, float, float]:
    """The weights as host arrays, and the seconds spent training (0
    where the file was kept) and loading. Logs the file's SHA-256, so
    that two checkouts can be seen to load the same bytes."""
    file = path(config, cache)
    train_s = 0.0
    if not os.path.exists(file):
        t = config["train"]
        t0 = time.perf_counter()
        imgs = gen_data.corpus(t["n_train"], t["seed"],
                               binarized=config["data"] == "binarized",
                               salt=TRAIN_SALT)
        params, bits = reference.train(
            config, imgs, steps=t["steps"], seed=t["seed"],
            batch=t["batch"], lr=t["lr"])
        flat = {f"{layer}/{k}": np.asarray(v)
                for layer, d in params.items() for k, v in d.items()}
        train_s = time.perf_counter() - t0
        log(f"weights: trained {t['steps']} steps on {t['n_train']} "
            f"images in {train_s:.2f} s, training -ELBO {bits:.4f} "
            f"bits/dim (last 100 batches)")
        os.makedirs(cache, exist_ok=True)
        tmp = file + ".tmp.npz"
        np.savez(tmp, **flat)
        os.replace(tmp, file)
    t0 = time.perf_counter()
    with open(file, "rb") as f:
        raw = f.read()
    host: Host = {}
    with np.load(io.BytesIO(raw)) as z:
        for key in z.files:
            layer, k = key.split("/")
            host.setdefault(layer, {})[k] = z[key]
    load_s = time.perf_counter() - t0
    log(f"weights: {os.path.basename(file)}, sha256 "
        f"{hashlib.sha256(raw).hexdigest()[:16]}")
    return host, train_s, load_s

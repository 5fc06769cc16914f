"""The program's host spans (``repro.spans``) on the float coding path:
a tiny VAE corpus through ``compress_corpus`` and ``decompress_dataset``
under the profiler. Every span name appears; the model's forward is
spanned exactly twice per chain step and direction (so no span fires
while a program is traced); only host reads nest, inside a lowering or
a frame, so no span covers a chain step; the wire bytes do not depend
on the profiler."""

from __future__ import annotations

import glob
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import shard_codec, spans
from repro.launch import compress
from repro.models import vae

CHAIN = 2        # images per lane
LANES = 4
NAMES = (spans.FORWARD, spans.LOWER, spans.CODER, spans.HOST_READ,
         spans.FRAME)
#: (outer, inner) pairs that may nest; nothing else does.
NESTS = {(spans.LOWER, spans.HOST_READ), (spans.FRAME, spans.HOST_READ)}


def _trace_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name.split("#")[0], e.start_ns, e.end_ns,
                         line.name)
                        for e in line.events if e.end_ns > e.start_ns]
    return out


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    cfg = vae.VAEConfig(input_dim=16, hidden=8, latent=4)
    params = vae.init(jax.random.PRNGKey(0), cfg)
    data = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                                (CHAIN, LANES, cfg.input_dim)
                                ).astype(jnp.int32)
    codec = vae.make_bb_codec(params, cfg)

    def encode():
        return compress.compress_corpus(codec, data, n_shards=1,
                                        block_symbols=CHAIN, seed=5)

    def decode(blob):
        return np.asarray(shard_codec.decompress_dataset(
            codec, blob, compile=True))

    blob_off = encode()                  # compiles every program
    decode(blob_off)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation("test.encode"):
            blob_on = encode()
        with jax.profiler.TraceAnnotation("test.decode"):
            out = decode(blob_on)
    return {"events": _trace_events(log_dir), "blob_off": blob_off,
            "blob_on": blob_on, "out": out, "data": np.asarray(data)}


def _inside(events, direction):
    (_, s, e, _), = [ev for ev in events if ev[0] == f"test.{direction}"]
    return [ev for ev in events
            if ev[0].startswith("repro.") and s <= ev[1] and ev[2] <= e]


def test_every_span_name_appears(run):
    seen = {ev[0] for ev in run["events"]}
    for name in NAMES:
        assert name in seen, name


@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_forward_twice_per_chain_step(run, direction):
    fwd = [ev for ev in _inside(run["events"], direction)
           if ev[0] == spans.FORWARD]
    assert len(fwd) == 2 * CHAIN


@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_only_host_reads_nest(run, direction):
    own = _inside(run["events"], direction)
    assert own
    for a, b in itertools.permutations(own, 2):
        if a[3] == b[3] and a[1] <= b[1] and b[2] <= a[2]:
            assert (a[0], b[0]) in NESTS, (a, b)


def test_blob_identical_with_the_profiler_on(run):
    assert run["blob_on"] == run["blob_off"]
    assert (run["out"] == run["data"]).all()

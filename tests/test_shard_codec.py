"""Lane-sharded dataset coding: split/merge, BBX3 framing, shard
independence, SPMD coder parity - and the PR-5 determinism contract:
multi-device wire bytes are identical to single-device bytes, proved
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in a
subprocess (the in-process backend is already initialized 1-device).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs, shard_codec, stream
from repro.core import ans
from repro.sharding import api as shard_api
from repro.stream import format as fmt


def _uniform_data(n=12, lanes=8, bits=6, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 1 << bits, (n, lanes)), jnp.int32)


# ---------------------------------------------------------------------------
# lane split/merge
# ---------------------------------------------------------------------------

def test_split_merge_lanes_roundtrip():
    stack = ans.make_stack(8, 16, key=jax.random.PRNGKey(0))
    stack = ans.seed_stack(stack, jax.random.PRNGKey(1), 4)
    shards = ans.split_lanes(stack, 4)
    assert all(s.lanes == 2 and s.capacity == 16 for s in shards)
    merged = ans.merge_lanes(shards)
    for a, b in zip(merged, stack):
        assert jnp.array_equal(a, b)


def test_split_lanes_coding_is_shard_local():
    """Coding a shard then merging == coding the same lanes unsplit."""
    codec = codecs.Uniform(6)
    xs = _uniform_data(n=1, lanes=8)[0]
    full = codecs.fresh_stack(8, 32, seed=0)
    shards = list(ans.split_lanes(full, 4))
    shards = [codec.push(s, xs[i * 2:(i + 1) * 2])
              for i, s in enumerate(shards)]
    merged = ans.merge_lanes(shards)
    ref = codec.push(full, xs)
    assert jnp.array_equal(merged.head, ref.head)
    assert jnp.array_equal(merged.buf, ref.buf)
    assert jnp.array_equal(merged.ptr, ref.ptr)


def test_split_lanes_rejects_nondivisible():
    stack = ans.make_stack(6, 8)
    with pytest.raises(ValueError):
        ans.split_lanes(stack, 4)
    with pytest.raises(ValueError):
        ans.merge_lanes([])


# ---------------------------------------------------------------------------
# BBX3 framing
# ---------------------------------------------------------------------------

def test_corpus_framing_roundtrip():
    segs = [b"shard-zero", b"s1", b"the-third-shard"]
    blob = fmt.encode_corpus(segs, [10, 2, 7], lanes_per_shard=2)
    header, entries = fmt.scan_corpus(blob)
    assert header.n_shards == 3 and header.lanes_per_shard == 2
    assert [e.n_symbols for e in entries] == [10, 2, 7]
    for s, seg in enumerate(segs):
        assert fmt.corpus_segment(blob, s) == seg
        e = entries[s]
        assert blob[e.offset:e.offset + e.length] == seg


def test_corpus_framing_rejects_corruption():
    blob = fmt.encode_corpus([b"abc"], [1], lanes_per_shard=1)
    with pytest.raises(ValueError):
        fmt.scan_corpus(b"BBQ3" + blob[4:])     # magic
    with pytest.raises(ValueError):
        fmt.scan_corpus(blob[:-2])              # truncated segment
    with pytest.raises(ValueError):
        fmt.corpus_segment(blob, 1)             # shard out of range
    with pytest.raises(ValueError):
        fmt.encode_corpus([], [], lanes_per_shard=1)


# ---------------------------------------------------------------------------
# dataset compress/decompress
# ---------------------------------------------------------------------------

def test_dataset_roundtrip_and_shard_independence():
    xs = _uniform_data(n=10, lanes=8)
    codec = codecs.Uniform(6)
    blob = shard_codec.compress_dataset(codec, xs, n_shards=4,
                                        block_symbols=3, seed=None,
                                        init_chunks=0)
    assert jnp.array_equal(shard_codec.decompress_dataset(codec, blob),
                           xs)
    # every shard decodes alone, from its segment bytes only
    for s in range(4):
        out = shard_codec.decompress_shard(codec, blob, s)
        assert jnp.array_equal(out, xs[:, s * 2:(s + 1) * 2])
    info = shard_codec.corpus_info(blob)
    assert info["n_shards"] == 4 and info["lanes_per_shard"] == 2
    assert info["total_symbols"] == 4 * 10
    assert sum(info["shard_bytes"]) + info["index_bytes"] == len(blob)


def test_dataset_chunked_input_matches_one_shot():
    xs = _uniform_data(n=9, lanes=4, seed=3)
    codec = codecs.Uniform(6)
    kw = dict(n_shards=2, block_symbols=4, seed=0, init_chunks=0)
    one = shard_codec.compress_dataset(codec, xs, **kw)
    chunked = shard_codec.compress_dataset(
        codec, [xs[:2], xs[2:7], xs[7:]], **kw)
    assert chunked == one


def test_dataset_bytes_independent_of_device_placement():
    """Same shard layout, forced single-device placement -> same blob."""
    xs = _uniform_data(n=6, lanes=8, seed=4)
    codec = codecs.Uniform(6)
    kw = dict(n_shards=4, block_symbols=2, seed=1, init_chunks=0)
    auto = shard_codec.compress_dataset(codec, xs, **kw)
    pinned = shard_codec.compress_dataset(
        codec, xs, devices=[jax.devices()[0]] * 4, **kw)
    assert pinned == auto


def test_dataset_bitsback_codec_roundtrip():
    """A BBANS codec (posterior pops -> per-block clean bits) through
    the sharded path."""
    bits = 6
    codec = codecs.BBANS(
        prior=codecs.Uniform(bits),
        likelihood=lambda y: codecs.Bernoulli((y - 32.0) / 8.0),
        posterior=lambda s: codecs.DiscretizedGaussian(
            2.0 * s - 1.0, jnp.full(s.shape, 0.5), bits))
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.integers(0, 2, (8, 4)), jnp.int32)
    blob = shard_codec.compress_dataset(codec, xs, n_shards=2,
                                        block_symbols=4, seed=0)
    assert jnp.array_equal(shard_codec.decompress_dataset(codec, blob),
                           xs)


def test_dataset_rejects_bad_layout():
    xs = _uniform_data(n=4, lanes=6)
    with pytest.raises(ValueError):
        shard_codec.compress_dataset(codecs.Uniform(6), xs, n_shards=4,
                                     block_symbols=2, seed=None,
                                     init_chunks=0)
    with pytest.raises(ValueError):
        shard_codec.compress_dataset(codecs.Uniform(6), [], n_shards=2,
                                     block_symbols=2)
    with pytest.raises(ValueError):
        shard_codec.split_lane_tree(xs, 4)


# ---------------------------------------------------------------------------
# SPMD coder programs (lane mesh; 1 device in-process)
# ---------------------------------------------------------------------------

def test_lane_mesh_compiled_codec_byte_parity():
    """Compiled-codec wire under use_lane_mesh == meshless wire."""
    rng = np.random.default_rng(0)
    lanes, n = 4, 16
    mu = jnp.asarray(rng.normal(0, 1, (lanes, n)), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.2, 1.5, (lanes, n)), jnp.float32)
    codec = codecs.Repeat(
        lambda d: codecs.DiscretizedGaussian(mu[:, d], sigma[:, d], 8),
        n)
    prog = codecs.compile(codec, donate=False)
    stack = codecs.fresh_stack(lanes, 128, seed=0, init_chunks=16)
    s_plain, y_plain = prog.pop(stack)
    with shard_api.use_lane_mesh(shard_api.lane_mesh()):
        s_mesh, y_mesh = prog.pop(stack)
        s_mesh = prog.push(s_mesh, y_mesh)
    s_plain = prog.push(s_plain, y_plain)
    assert jnp.array_equal(y_plain, y_mesh)
    assert jnp.array_equal(s_plain.head, s_mesh.head)
    assert jnp.array_equal(s_plain.buf, s_mesh.buf)


def test_lane_mesh_rejects_bad_shapes():
    with pytest.raises(ValueError):
        shard_api.lane_mesh(len(jax.devices()) + 1)
    from repro.codecs.compile import coder_programs
    with pytest.raises(ValueError):
        coder_programs(jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(1, 1), ("a", "b")))


# ---------------------------------------------------------------------------
# multi-device determinism (8 simulated host devices, subprocess)
# ---------------------------------------------------------------------------

MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import codecs, serve, shard_codec
    from repro.sharding import api as shard_api

    assert len(jax.devices()) == 8, jax.devices()
    rng = np.random.default_rng(0)
    lanes, n = 8, 6
    xs = jnp.asarray(rng.integers(0, 64, (n, lanes)), jnp.int32)
    codec = codecs.Uniform(6)

    # BBX3 corpus across 8 real (simulated) devices
    blob = shard_codec.compress_dataset(
        codec, xs, n_shards=8, block_symbols=2, seed=0, init_chunks=0)
    ok_rt = bool(jnp.array_equal(
        shard_codec.decompress_dataset(codec, blob), xs))

    # one-shot SPMD path: lane mesh over all 8 devices
    eng = serve.ShardedCodecEngine(
        lambda shape: codecs.Repeat(lambda d: codecs.Uniform(6),
                                    shape[0]),
        seed=0)
    data = xs.reshape(n, lanes, 1)             # [n, lanes, 1]
    one = eng.compress(data)
    ok_spmd = bool(jnp.array_equal(
        eng.decompress(one, n, (1,)), data))

    print(json.dumps({
        "devices": len(jax.devices()),
        "mesh": int(eng.mesh.devices.size),
        "blob": blob.hex(),
        "oneshot": one.hex(),
        "ok_rt": ok_rt, "ok_spmd": ok_spmd,
    }))
""")


@pytest.mark.slow
def test_multi_device_wire_matches_single_device():
    """The acceptance-criterion test: bytes produced on 8 simulated
    devices == bytes produced in this 1-device process, for both the
    BBX3 dataset path and the SPMD one-shot path."""
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_SCRIPT],
        capture_output=True, text=True, timeout=420,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8 and rec["mesh"] == 8
    assert rec["ok_rt"] and rec["ok_spmd"]

    # reproduce both blobs locally (1 device) - bytes must match
    rng = np.random.default_rng(0)
    lanes, n = 8, 6
    xs = jnp.asarray(rng.integers(0, 64, (n, lanes)), jnp.int32)
    codec = codecs.Uniform(6)
    local = shard_codec.compress_dataset(
        codec, xs, n_shards=8, block_symbols=2, seed=0, init_chunks=0)
    assert local.hex() == rec["blob"], \
        "BBX3 corpus bytes differ between 8 devices and 1 device"

    from repro import serve
    eng = serve.ShardedCodecEngine(
        lambda shape: codecs.Repeat(lambda d: codecs.Uniform(6),
                                    shape[0]),
        seed=0)
    data = xs.reshape(n, lanes, 1)
    one = eng.compress(data)
    assert one.hex() == rec["oneshot"], \
        "one-shot SPMD bytes differ between 8 devices and 1 device"


FLOAT_VAE_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from repro.launch import compress as cli
    from repro.models import vae
    from repro.serve import CodecEngine, ShardedCodecEngine

    cfg = vae.paper_config("bernoulli")
    codec = vae.make_bb_codec(vae.init(jax.random.PRNGKey(0), cfg), cfg)
    _, data, _ = cli.load_corpus("vae-bernoulli", 64, 32)
    sharded = ShardedCodecEngine(lambda s: codec, n_shards=4, seed=0)
    one = CodecEngine(lambda s: codec, seed=0, compile=True)
    blob = sharded.compress(data)
    print(json.dumps({
        "same": blob == one.compress(data),
        "lossless": bool(jnp.array_equal(
            sharded.decompress(blob, 2, (784,)), data)),
    }))
""")


def test_sharded_engine_float_vae_decodes_on_four_devices():
    """The float VAE's one-shot SPMD decode is lossless: the model runs
    on the popped symbols between coder programs, and lane-sharded
    symbols would let XLA partition its matmuls into other float bits
    than the encoder saw."""
    out = subprocess.run(
        [sys.executable, "-c", FLOAT_VAE_SPMD_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/tmp"),
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec == {"same": True, "lossless": True}


# ---------------------------------------------------------------------------
# ShardedCodecEngine (1 device in-process)
# ---------------------------------------------------------------------------

def test_sharded_engine_matches_codec_engine_and_decodes():
    from repro.serve.engine import CodecEngine, ShardedCodecEngine

    def family(shape):
        return codecs.Repeat(lambda d: codecs.Uniform(4), shape[0])

    rng = np.random.default_rng(7)
    data = jnp.asarray(rng.integers(0, 16, (3, 4, 5)), jnp.int32)
    base = CodecEngine(family, seed=0, compile=True)
    eng = ShardedCodecEngine(family, seed=0, n_shards=2)
    assert eng.compress(data) == base.compress(data)
    assert jnp.array_equal(
        eng.decompress(eng.compress(data), 3, (5,)), data)

    corp = eng.compress_dataset(data, block_symbols=2)
    assert jnp.array_equal(eng.decompress_dataset(corp, (5,)), data)
    assert jnp.array_equal(eng.decompress_shard(corp, 1, (5,)),
                           data[:, 2:])
    # a streaming loader (generator of chunks) produces the same corpus
    corp_gen = eng.compress_dataset(
        (c for c in [data[:1], data[1:]]), block_symbols=2)
    assert corp_gen == corp


def test_sharded_engine_rejects_bad_inputs():
    from repro.serve.engine import ShardedCodecEngine

    def family(shape):
        return codecs.Repeat(lambda d: codecs.Uniform(4), shape[0])

    eng = ShardedCodecEngine(family, seed=0, n_shards=1)
    with pytest.raises(ValueError, match="no data chunks"):
        eng.compress_dataset(iter([]))
    with pytest.raises(ValueError, match="no data chunks"):
        eng.compress_dataset([])
    # lanes not a multiple of the mesh size -> clear up-front error
    eng._check_lanes(4)                        # multiple of 1: fine
    eng2 = ShardedCodecEngine.__new__(ShardedCodecEngine)
    eng2.mesh = type("M", (), {"devices": np.zeros((2,))})()
    eng2._check_lanes(4)                       # 4 % 2 == 0: fine
    with pytest.raises(ValueError, match="multiple"):
        eng2._check_lanes(3)

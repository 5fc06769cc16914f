"""The main path's Pallas kernels compile for a TPU v5e chip.

Every coder kernel of the paper's VAE path is compiled ahead of time for
a *described* v5e chip (no chip attached): 1024 lanes, 784 pixel steps,
40 latent steps, ``lat_bits=10``, at both autotuner lane tiles (128 and
256), with ``interpret=False``. So are the fused fixed-point VAE encode
and decode programs, which inline those kernels. A kernel that Mosaic
refuses fails here, not on the chip.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import ans
from repro.kernels import dispatch
from repro.kernels.ans import kernel as ans_kernel
from repro.kernels.bucketize import kernel as bk_kernel

LANES = 1024
PIXELS = 784
LATENTS = 40
LAT_BITS = 10
PRECISION = 16
TILES = (128, 256)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, sharding, *shapes):
    args = [_spec(s, d, sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


U32, F32 = jnp.uint32, jnp.float32


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("steps", [PIXELS, LATENTS])
def test_push_emit_compiles(one_chip, tile, steps):
    _compile_kernel(
        lambda h, s, f: ans_kernel.push_emit(h, s, f, PRECISION,
                                             interpret=False,
                                             lane_tile=tile),
        one_chip, ((LANES,), U32), ((steps, LANES), U32),
        ((steps, LANES), U32))


@pytest.mark.parametrize("tile", TILES)
def test_pop_slots_compiles(one_chip, tile):
    _compile_kernel(
        lambda h: ans_kernel.pop_slots(h, PRECISION, interpret=False,
                                       lane_tile=tile),
        one_chip, ((LANES,), U32))


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("symbols", [2, 256])
def test_pop_table_emit_compiles(one_chip, tile, symbols):
    _compile_kernel(
        lambda h, t, f: ans_kernel.pop_table_emit(h, t, f, PRECISION,
                                                  interpret=False,
                                                  lane_tile=tile),
        one_chip, ((LANES,), U32), ((LANES, symbols + 1), U32),
        ((PIXELS, LANES), U32))


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("symbols", [2, 256])
def test_pop_dyntable_emit_compiles(one_chip, tile, symbols):
    _compile_kernel(
        lambda h, t, f: ans_kernel.pop_dyntable_emit(h, t, f, PRECISION,
                                                     interpret=False,
                                                     lane_tile=tile),
        one_chip, ((LANES,), U32), ((PIXELS, LANES, symbols + 1), U32),
        ((PIXELS, LANES), U32))


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("kind", ["gaussian", "logistic", "uniform"])
def test_pop_grid_emit_compiles(one_chip, tile, kind):
    k1 = (1 << LAT_BITS) + 1
    _compile_kernel(
        lambda h, m, s, f, e: ans_kernel.pop_grid_emit(
            h, m, s, f, e, kind, LAT_BITS, PRECISION, interpret=False,
            lane_tile=tile),
        one_chip, ((LANES,), U32), ((LATENTS, LANES), F32),
        ((LATENTS, LANES), F32), ((LATENTS, LANES), U32), ((k1,), F32))


@pytest.mark.parametrize("tile", TILES)
def test_bucketize_compiles(one_chip, tile):
    _compile_kernel(
        lambda sl, m, s: bk_kernel.bucketize(sl, m, s, LAT_BITS, PRECISION,
                                             interpret=False,
                                             lane_tile=tile),
        one_chip, ((LANES,), U32), ((LANES,), F32), ((LANES,), F32))


@pytest.mark.parametrize("program", ["push", "pop_dyn", "pop_grid"])
def test_lane_mesh_coder_programs_compile(topo, program):
    """The SPMD coder programs of ``ShardedCodecEngine``'s one-shot
    path, over a 4-chip lane mesh: the kernels run per shard, and the
    pops hand back their symbols replicated (an all-gather)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.codecs.compile import _mesh_coder_programs

    mesh = Mesh(topo.devices, ("lanes",))
    stack = jax.eval_shape(lambda: ans.make_stack(LANES, 4 * PIXELS))
    stack = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, NamedSharding(
            mesh, P("lanes", *[None] * (len(s.shape) - 1)))), stack)
    rows = NamedSharding(mesh, P(None, "lanes"))
    pallas = dispatch.Decision("pallas")
    jitted = _mesh_coder_programs(mesh)[program][False]
    if program == "push":
        lowered = jitted.lower(stack, _spec((PIXELS, LANES), U32, rows),
                               _spec((PIXELS, LANES), U32, rows),
                               precision=PRECISION, backend=pallas)
    elif program == "pop_dyn":
        tables = _spec((PIXELS, LANES, 3), U32,
                       NamedSharding(mesh, P(None, "lanes", None)))
        lowered = jitted.lower(stack, tables, precision=PRECISION,
                               backend=pallas)
    else:
        lowered = jitted.lower(
            stack, mu=_spec((LATENTS, LANES), F32, rows),
            sigma=_spec((LATENTS, LANES), F32, rows), kind="gaussian",
            steps=LATENTS, lat_bits=LAT_BITS, precision=PRECISION,
            backend=pallas)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    if program != "push":
        assert "all-gather" in text, "popped symbols are not replicated"


@pytest.fixture(scope="module")
def fused_vae():
    """The paper's VAE (784-100-40, Bernoulli) as the fused fixed-point
    codec over a 4-image chain; random weights from a seed."""
    from repro import codecs
    from repro.models import vae

    cfg = vae.paper_config("bernoulli")
    params = vae.init(jax.random.PRNGKey(0), cfg)
    bb = vae.make_bb_codec_q(params, cfg)
    return codecs.compile(codecs.Chained(bb, 4)).lowered


def _stack_spec(sharding, capacity):
    shapes = jax.eval_shape(lambda: ans.make_stack(LANES, capacity))
    return jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, sharding), shapes)


@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_fused_fixed_point_vae_compiles(one_chip, fused_vae, direction):
    pallas = dispatch.Decision("pallas")
    stack = _stack_spec(one_chip, 4096)
    if direction == "encode":
        data = _spec((4, LANES, PIXELS), jnp.int32, one_chip)
        lowered = fused_vae._push.lower(stack, data, backend=pallas)
    else:
        lowered = fused_vae._pop.lower(stack, backend=pallas)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"

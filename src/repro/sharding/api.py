"""Logical-axis sharding API: models constrain activations by *logical*
names; launch code binds logical names to mesh axes.

Keeps model code mesh-agnostic (the 1000-node posture): the same forward
runs unsharded in unit tests, on a (data, model) pod, or on a
(pod, data, model) multi-pod mesh, with only the rule binding changing.

Two thread-local contexts live here:

  * ``use_mesh``      - the model-sharding context consumed by
    ``constrain`` (training/serving activations and parameters);
  * ``use_lane_mesh`` - the *coder*-sharding context consumed by the
    codec compiler (``codecs.compile``): while active, compiled codecs
    run their integer coder programs SPMD over the ANS lane axis via
    ``shard_map`` (docs/SCALING.md). They are independent on purpose -
    a codec service shards lanes without adopting model-parallel rules.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: The mesh axis name every lane-sharded coder program shards over.
LANE_AXIS = "lanes"

Axes = Union[None, str, Tuple[str, ...]]

#: Default logical->mesh binding for the production meshes (DESIGN.md 5).
DEFAULT_RULES: Dict[str, Axes] = {
    "batch": ("pod", "data"),
    "seq": None,            # sequence usually replicated; SP binds to model
    "kv_seq": None,         # decode KV sequence; SP binds leftover model
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "fsdp": ("pod", "data"),  # parameter dim sharded ZeRO-3 style
    "lanes": ("pod", "data"),  # ANS coder lanes (embarrassingly parallel)
}


class _Env(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, Axes]] = None
    lane_mesh: Optional[Mesh] = None


_ENV = _Env()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, Axes]] = None):
    """Bind a mesh + logical rules for ``constrain`` within the context.

    Example::

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with use_mesh(mesh, {"seq": "model"}):
            y = constrain(x, "batch", "seq")   # sharded inside jit
    """
    prev = (_ENV.mesh, _ENV.rules)
    _ENV.mesh = mesh
    _ENV.rules = dict(DEFAULT_RULES, **(rules or {})) if mesh else None
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _ENV.mesh, _ENV.rules = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh bound by the innermost ``use_mesh`` (None outside)."""
    return _ENV.mesh


# ---------------------------------------------------------------------------
# lane meshes (ANS coder data-parallelism; see docs/SCALING.md)
# ---------------------------------------------------------------------------

def lane_mesh(n_shards: Optional[int] = None) -> Mesh:
    """A 1-D mesh over local devices for lane-axis coder sharding.

    ``n_shards`` defaults to every local device; fewer is allowed (the
    leading devices are used). The single axis is named ``LANE_AXIS`` -
    the name ``shard_map``-wrapped coder programs and the ``lanes``
    entry of ``DEFAULT_RULES`` both resolve against.

    Example::

        mesh = lane_mesh()                     # all local devices
        with use_lane_mesh(mesh):
            blob = codecs.compress(compiled_codec, data, lanes=16)
    """
    devices = jax.devices()
    n = len(devices) if n_shards is None else n_shards
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"sharding.lane_mesh: need 1 <= n_shards <= "
            f"{len(devices)} local devices, got {n_shards}")
    return Mesh(np.asarray(devices[:n]), (LANE_AXIS,))


@contextlib.contextmanager
def use_lane_mesh(mesh: Optional[Mesh]):
    """Bind a lane mesh for compiled-codec coder programs.

    Within the context, ``codecs.compile``'d codecs route their fused
    integer coder calls through ``shard_map`` over ``mesh`` - one SPMD
    program, lanes split across devices, wire bytes identical to the
    meshless path (integer coder ops are exact in any partitioning).
    The stack's lane count must be a multiple of the mesh size.

    Example::

        with use_lane_mesh(lane_mesh()):
            stack = prog.push(stack, xs)       # lanes split over devices
    """
    prev = _ENV.lane_mesh
    _ENV.lane_mesh = mesh
    try:
        yield
    finally:
        _ENV.lane_mesh = prev


def current_lane_mesh() -> Optional[Mesh]:
    """The mesh bound by the innermost ``use_lane_mesh`` (None outside)."""
    return _ENV.lane_mesh


def resolve(*logical: Optional[str]) -> P:
    """Map logical axis names to a PartitionSpec under the active rules.

    Names that are unbound (or when no mesh is active) resolve to None.
    Mesh axes that don't exist on the active mesh are dropped - this is what
    lets the same rules serve the single-pod mesh (no 'pod' axis).

    Example::

        with use_mesh(jax.make_mesh((4,), ("data",),
                                    axis_types=(AxisType.Auto,))):
            assert resolve("batch", "embed") == P("data", None)
    """
    rules = _ENV.rules or {}
    mesh_axes = set(_ENV.mesh.axis_names) if _ENV.mesh is not None else set()

    def one(name):
        if name is None:
            return None
        ax = rules.get(name)
        if ax is None:
            return None
        if isinstance(ax, str):
            return ax if ax in mesh_axes else None
        kept = tuple(a for a in ax if a in mesh_axes)
        return kept if kept else None

    # A mesh axis may appear at most once in a spec: first logical name
    # wins (e.g. with SP bound, "seq" takes 'model' and later names that
    # also resolve to 'model' fall back to replicated).
    used = set()
    out = []
    for n in logical:
        entry = one(n)
        if isinstance(entry, str):
            entry = None if entry in used else entry
            if entry:
                used.add(entry)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a not in used)
            used.update(kept)
            entry = kept if kept else None
        out.append(entry)
    return P(*out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh.

    Example::

        h = constrain(h, "batch", None, "ff")   # inside a jitted step
    """
    mesh = _ENV.mesh
    if mesh is None:
        return x
    spec = resolve(*logical)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def named_sharding(*logical: Optional[str]) -> Optional[NamedSharding]:
    """``NamedSharding`` for the logical names under the active mesh
    (None without one) - the ``jit(in_shardings=...)`` form of
    ``constrain``.

    Example::

        sh = named_sharding("batch")            # place a batch leaf
        batch = jax.device_put(batch, sh) if sh else batch
    """
    mesh = _ENV.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical))

"""Backend dispatch for the coder kernels: pallas / xla / interpret.

Every hot op in ``kernels/ans/ops.py`` and ``kernels/bucketize/ops.py``
has up to three bit-identical implementations:

  * ``"pallas"``    - ``pl.pallas_call`` compiled through Mosaic. Only
                      available on TPU (the kernels are written for
                      Mosaic's layouts and compiler parameters).
  * ``"xla"``       - the pure-XLA twins in ``kernels/*/xla.py``: same
                      loop bodies jitted straight through XLA, no lane
                      padding, tunable ``fori_loop`` unroll. The CPU
                      fast path.
  * ``"interpret"`` - ``pl.pallas_call(interpret=True)``: the Pallas
                      interpreter emulating the kernel. Runs anywhere,
                      slowly: the CPU tests use it to check the
                      kernels, and it is only ever used when asked
                      for by name.

``resolve(op, ...)`` picks one as a :class:`Decision` (backend +
lane-tile + unroll), with precedence:

  1. an explicit ``backend=`` argument (string or ``Decision``),
  2. the ``REPRO_KERNEL_BACKEND`` environment variable,
  3. an enclosing ``with use_backend(...)`` context,
  4. the persisted tuning cache (``kernels.tuning``, measured once;
     it never holds ``interpret``),
  5. the platform heuristic: ``pallas`` on TPU, ``xla`` elsewhere.

Wire bytes never depend on the choice - the parity suite
(``tests/test_dispatch.py``) pins every available backend to the
``ref.py`` oracles and the committed golden fixtures.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Iterator, Optional, Tuple, Union

import jax

BACKENDS = ("pallas", "xla", "interpret")

# Default Pallas lane tile; kernels accept other multiples of the VPU
# width via Decision.lane_tile (the autotuner's tiling candidates).
DEFAULT_LANE_TILE = 128

_ENV_BACKEND = "REPRO_KERNEL_BACKEND"


@dataclasses.dataclass(frozen=True)
class Decision:
    """One resolved kernel choice. Frozen + hashable, so a Decision can
    ride through ``jax.jit`` as a static argument - the tuner times
    candidates by passing them straight to the public ops."""

    backend: str
    lane_tile: int = DEFAULT_LANE_TILE   # pallas/interpret tile width
    unroll: int = 1                      # xla fori_loop unroll factor

    def __post_init__(self) -> None:
        if self.backend not in BACKENDS:
            raise ValueError(
                f"kernels.dispatch: unknown backend {self.backend!r} "
                f"(expected one of {BACKENDS})")
        if self.lane_tile < 1 or self.unroll < 1:
            raise ValueError(
                "kernels.dispatch: lane_tile and unroll must be >= 1 "
                f"(got lane_tile={self.lane_tile}, unroll={self.unroll})")


BackendLike = Union[None, str, Decision]

class _ContextStack(threading.local):
    """Per-thread ``use_backend()`` stack (innermost last): the serve
    engines pin backends per request from a thread pool, so one
    request's pin must not leak into a concurrent one."""

    def __init__(self) -> None:
        self.stack: list = []


_CONTEXT = _ContextStack()


def platform() -> str:
    """The active JAX platform ("cpu", "tpu", "gpu", ...)."""
    return jax.default_backend()


def available_backends(plat: Optional[str] = None) -> Tuple[str, ...]:
    """Backends that can actually run on ``plat`` (default: active
    platform), best-first. ``pallas`` compiled mode needs the Mosaic
    lowering, so it is only offered on TPU."""
    p = plat if plat is not None else platform()
    if p == "tpu":
        return ("pallas", "xla", "interpret")
    return ("xla", "interpret")


def _normalize(backend: BackendLike) -> Optional[Decision]:
    if backend is None:
        return None
    if isinstance(backend, Decision):
        return backend
    return Decision(backend=backend)


@contextlib.contextmanager
def use_backend(backend: Union[str, Decision]) -> Iterator[Decision]:
    """Force a backend for every dispatched op in the ``with`` body
    (unless a call passes an explicit ``backend=``). Nests; innermost
    wins. The serve engines and benchmark pins use this.

    Example::

        with use_backend("xla"):
            blob = engine.compress(data)
    """
    decision = _normalize(backend)
    _CONTEXT.stack.append(decision)
    try:
        yield decision
    finally:
        _CONTEXT.stack.pop()


def resolve(op: str, lanes: Optional[int] = None,
            table_size: Optional[int] = None,
            backend: BackendLike = None) -> Decision:
    """Resolve ``op`` to a concrete :class:`Decision`.

    ``lanes`` / ``table_size`` describe the workload for the tuning
    cache; they do not change which backends are legal. Resolution is
    pure lookup - it never times anything (measured autotuning is
    explicit: ``kernels.tuning.autotune``).
    """
    explicit = _normalize(backend)
    if explicit is not None:
        return explicit

    env = os.environ.get(_ENV_BACKEND)
    if env:
        return Decision(backend=env)

    if _CONTEXT.stack:
        return _CONTEXT.stack[-1]

    from repro.kernels import tuning
    cached = tuning.lookup(platform(), op, lanes=lanes,
                           table_size=table_size)
    if cached is not None:
        return cached

    return Decision(backend=available_backends()[0])

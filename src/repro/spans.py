"""Host spans at the boundaries of the float coding path's layers.

Every span is a ``jax.profiler.TraceAnnotation``: with the profiler off
it costs about a microsecond; under ``jax.profiler.trace`` it lands in
the trace's host plane, on the device trace's clock, so each idle gap
of the device can be named by the layer the host was in.

=================  ====================================================
``FORWARD``        the model's networks and the leaf codec built on
                   them (``models/vae.make_bb_codec``'s closures)
``LOWER``          one lowering of a ``Repeat`` (``codecs/compile``):
                   probes, tables or grid parameters, their validation
``CODER``          the dispatch of one fused coder program
``HOST_READ``      one blocking device-to-host read (``host_read``);
                   its ``site`` is the span's metadata
``FRAME``          the host's byte work: block, header and corpus
                   framing (``stream``, ``shard_codec``)
=================  ====================================================

Spans never enclose a chain step, a stream block or a whole pass, and
only ``HOST_READ`` nests (inside ``LOWER`` or ``FRAME``), so the
outermost span open at an idle gap names its layer. No span is opened
inside a function that JAX traces: there it would fire once, at trace
time, and measure nothing.

Example::

    @spans.spanned(spans.CODER)
    def push(self, stack, x):
        return program(stack, x)

    n_over = int(spans.host_read(jnp.sum(stack.overflows), "overflows"))
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import numpy as np

FORWARD = "repro.forward"
LOWER = "repro.lower"
CODER = "repro.coder"
HOST_READ = "repro.host_read"
FRAME = "repro.frame"


def spanned(name: str) -> Callable[[Callable], Callable]:
    """Decorator: every call of the function runs inside a span
    ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def host_read(x: Any, site: str) -> np.ndarray:
    """``np.asarray(x)``: wait for ``x`` and copy it to host memory,
    inside a ``HOST_READ`` span whose metadata names ``site``."""
    with jax.profiler.TraceAnnotation(HOST_READ, site=site):
        return np.asarray(x)

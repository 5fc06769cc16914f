"""Codec compiler: lower ``Codec`` trees to fused kernel-backed programs.

The interpreted combinators (``Repeat``/``Serial``/``BBANS``/...) run
one ``ans.push``/``ans.pop`` per Python-level dispatch: every symbol
pays a full-stack scatter and a host dispatch. This module removes
that cost by *lowering* the tree (``_lower``): ``Repeat`` nodes are
probed - ``codec_fn(d)`` is called for every position - and when the
per-position leaves are a recognized family with stackable parameters
they collapse into one vectorized node:

  * ``Uniform`` / ``DiscretizedGaussian`` / ``DiscretizedLogistic``
    -> ``_GridRepeat``: encode gathers all [n, lanes] (start, freq)
    pairs in one shot and makes a single multi-step
    ``kernels.ans.ops.push_many`` call; decode is one fused
    bucketize+pop kernel call (``ops.pop_many_grid`` - the CDF
    bisection of ``kernels/bucketize`` inside the ANS renorm chain).
  * ``Bernoulli`` / ``Categorical`` / ``BetaBinomial`` ->
    ``_TableRepeat``: per-step cumulative-starts tables, one
    ``push_many`` / ``pop_many_dyn`` (dynamic-table kernel) call.

Unrecognized or heterogeneous ``Repeat`` bodies (and plain leaves,
``FnCodec``s, ...) fall back to their interpreted form - still
correct, just not fused. Function-valued children (``BBANS``
likelihood/posterior, ``BitSwap`` layers) are lowered lazily at call
time, so closures over network outputs lower too.

**The determinism contract** (why there is no single whole-tree jit):
coding is only lossless if encoder and decoder compute bit-identical
fixed-point CDFs, and float32 results in XLA depend on the fusion
context - the same ``exp``/``ndtr`` chain fused into two different
programs can differ by one ulp, which flips a ``floor`` one time in
~10^4 and corrupts the stream. The compiler therefore keeps every
model-float evaluation (networks, CDF starts, tables) in *canonical
eager form* - bit-identical to the interpreted path by construction -
and fuses the **integer** coder loops into a handful of jitted
programs with donated ``ANSStack`` buffers (integer ops are exact in
any context). The Gaussian/logistic CDF chain is additionally written
in its XLA-canonical form (concrete edge tables, reciprocal-multiply
standardization - see ``core.discretize``), which makes the fused
in-kernel CDF inversion bit-stable too; ``tests/test_compile.py``
enforces all of this at scale. Wire bytes are **identical** to the
interpreted path.

Example::

    prog = codecs.compile(codecs.Chained(make_bb_codec(p, cfg), n))
    blob = codecs.compress(prog, data, lanes=16, seed=0)
    assert blob == codecs.compress(interpreted, data, lanes=16, seed=0)

Import note: ``codecs.compile`` (the function re-exported by
``repro.codecs``) shadows this module's dotted path; use
``from repro.codecs.compile import ...`` for the internals.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ans, discretize
from repro.core.codec import Codec
from repro.core.distributions import (Bernoulli, BetaBinomial, Categorical,
                                      _stable_softmax,
                                      beta_binomial_log_pmf)
from repro.codecs import combinators as C
from repro.codecs import leaves as L
from repro.codecs import quantize as Q
from repro import spans
from repro.kernels import dispatch
from repro.kernels.ans import ops as ans_ops


# ---------------------------------------------------------------------------
# jitted integer coder programs (shared across all compiled codecs)
# ---------------------------------------------------------------------------
# The ANSStack argument is donated in the True variants so encode and
# decode update the coder state in place; drivers never reuse an input
# stack, tests that do should compile with donate=False.
#
# ``backend`` is a ``kernels.dispatch.Decision`` (hashable -> a valid
# static arg): the fused nodes resolve it eagerly per call, so
# ``use_backend``/``REPRO_KERNEL_BACKEND``/the tuning cache steer even
# already-compiled codecs, at the cost of one retrace per distinct
# Decision.

def _coder_jits(fn, static):
    return {
        True: jax.jit(fn, static_argnames=static, donate_argnums=(0,)),
        False: jax.jit(fn, static_argnames=static),
    }


def _push_grid_body(stack, idxT, mu, sigma, *, kind, bits, precision,
                    backend=None):
    """Grid push with the starts evaluation INSIDE the jit.

    The eager-starts hop used to dominate compiled grid encode; the CDF
    chain is the canonical fusion-stable form (concrete edge tables,
    reciprocal-multiply - the decode side already evaluates it inside
    ``pop_many_grid``'s fused bisection), so tracing it here keeps the
    wire bytes identical while removing the host round-trip.
    """
    if kind == "uniform":
        shift = precision - bits
        start = idxT.astype(jnp.uint32) << shift
        freq = jnp.full_like(start, jnp.uint32(1 << shift))
    else:
        if kind == "gaussian":
            f = discretize.posterior_starts_fn(mu, sigma, bits, precision)
        else:
            f = L.logistic_starts_fn(mu, sigma, bits, precision)
        start = f(idxT)
        freq = f(idxT + 1) - start
    return ans_ops.push_many(stack, start[::-1], freq[::-1],
                             precision=precision, backend=backend)


def _push_table_body(stack, tables, symT, *, precision, backend=None):
    """Table push with the per-step starts gather INSIDE the jit
    (integer gather: exact in any fusion context)."""
    sym = symT[..., None]                                 # [n, lanes, 1]
    start = jnp.take_along_axis(tables, sym, axis=2)[..., 0]
    nxt = jnp.take_along_axis(tables, sym + 1, axis=2)[..., 0]
    return ans_ops.push_many(stack, start[::-1].astype(jnp.uint32),
                             (nxt - start)[::-1].astype(jnp.uint32),
                             precision=precision, backend=backend)


_PUSH_MANY = _coder_jits(ans_ops.push_many, ("precision", "backend"))
_POP_DYN = _coder_jits(ans_ops.pop_many_dyn, ("precision", "backend"))
_POP_GRID = _coder_jits(
    ans_ops.pop_many_grid,
    ("kind", "steps", "lat_bits", "precision", "backend"))
_PUSH_GRID = _coder_jits(
    _push_grid_body, ("kind", "bits", "precision", "backend"))
_PUSH_TABLE = _coder_jits(_push_table_body, ("precision", "backend"))


# ---------------------------------------------------------------------------
# mesh-sharded coder programs (lane-axis SPMD; see docs/SCALING.md)
# ---------------------------------------------------------------------------
# Under ``sharding.api.use_lane_mesh``, the fused nodes below swap the
# shared jits for shard_map-wrapped twins: one SPMD program per
# direction, the ANSStack lane axis (and every per-lane operand axis)
# split across the mesh. Integer coder ops are exact in any
# partitioning context, so the wire bytes are identical to the
# meshless path - the PR-4 determinism contract extends to devices.
# Programs are cached per mesh (the compiled executables are keyed by
# the device set, so two meshes over the same devices share nothing).

def _stack_spec(axis: str) -> ans.ANSStack:
    from jax.sharding import PartitionSpec as P
    return ans.ANSStack(head=P(axis), buf=P(axis, None), ptr=P(axis),
                        underflows=P(axis), overflows=P(axis))


def _mesh_coder_programs(mesh) -> Dict[str, Any]:
    """The shard_map'd twins of the three fused coder entry points."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    (axis,) = mesh.axis_names
    st = _stack_spec(axis)
    lane1 = P(None, axis)          # [steps, lanes]

    def push(stack, starts, freqs, *, precision, backend=None):
        return jax.shard_map(
            lambda s, a, f: ans_ops.push_many(
                s, a, f, precision=precision, backend=backend),
            mesh=mesh, in_specs=(st, lane1, lane1), out_specs=st,
            check_vma=False)(stack, starts, freqs)

    # Popped symbols leave as replicated arrays: the eager float model
    # that consumes them next then runs at single-device shapes on
    # every device, so its float bits (and the tables built from them)
    # are the meshless path's. On lane-sharded inputs XLA may
    # partition the model's matmuls differently, and a decode whose
    # posterior push sees other bits than the encoder's pop is lossy.
    replicated = NamedSharding(mesh, P())

    def pop_dyn(stack, tables, *, precision, backend=None):
        stack, syms = jax.shard_map(
            lambda s, t: ans_ops.pop_many_dyn(
                s, t, precision=precision, backend=backend),
            mesh=mesh, in_specs=(st, P(None, axis, None)),
            out_specs=(st, lane1), check_vma=False)(stack, tables)
        return stack, jax.lax.with_sharding_constraint(syms, replicated)

    def pop_grid(stack, *, mu, sigma, kind, steps, lat_bits, precision,
                 backend=None):
        spec = lane1 if jnp.ndim(mu) == 2 else P()
        stack, syms = jax.shard_map(
            lambda s, m, g: ans_ops.pop_many_grid(
                s, kind, m, g, steps, lat_bits, precision=precision,
                backend=backend),
            mesh=mesh, in_specs=(st, spec, spec),
            out_specs=(st, lane1), check_vma=False)(stack, mu, sigma)
        return stack, jax.lax.with_sharding_constraint(syms, replicated)

    def push_grid(stack, idxT, mu, sigma, *, kind, bits, precision,
                  backend=None):
        spec = lane1 if jnp.ndim(mu) == 2 else P()
        return jax.shard_map(
            lambda s, i, m, g: _push_grid_body(
                s, i, m, g, kind=kind, bits=bits, precision=precision,
                backend=backend),
            mesh=mesh, in_specs=(st, lane1, spec, spec), out_specs=st,
            check_vma=False)(stack, idxT, mu, sigma)

    def push_table(stack, tables, symT, *, precision, backend=None):
        return jax.shard_map(
            lambda s, t, y: _push_table_body(
                s, t, y, precision=precision, backend=backend),
            mesh=mesh, in_specs=(st, P(None, axis, None), lane1),
            out_specs=st, check_vma=False)(stack, tables, symT)

    return {
        "push": _coder_jits(push, ("precision", "backend")),
        "pop_dyn": _coder_jits(pop_dyn, ("precision", "backend")),
        "pop_grid": _coder_jits(
            pop_grid,
            ("kind", "steps", "lat_bits", "precision", "backend")),
        "push_grid": _coder_jits(
            push_grid, ("kind", "bits", "precision", "backend")),
        "push_table": _coder_jits(push_table, ("precision", "backend")),
    }


#: program cache keyed by mesh: Mesh is hashable on (devices, axis
#: names), exactly the identity of the lowered SPMD executables.
_MESH_PROGRAMS: Dict[Any, Dict[str, Any]] = {}


def coder_programs(mesh: Optional[Any] = None) -> Dict[str, Any]:
    """The active coder programs: shared jits, or the ``mesh``-sharded
    twins (built once per mesh and cached).

    Example::

        progs = coder_programs(sharding.lane_mesh())
        stack = progs["push"][True](stack, starts, freqs, precision=16)
    """
    if mesh is None:
        return {"push": _PUSH_MANY, "pop_dyn": _POP_DYN,
                "pop_grid": _POP_GRID, "push_grid": _PUSH_GRID,
                "push_table": _PUSH_TABLE}
    if mesh not in _MESH_PROGRAMS:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"codecs.compile: lane meshes are 1-D, got axes "
                f"{mesh.axis_names} (build one with sharding.lane_mesh)")
        _MESH_PROGRAMS[mesh] = _mesh_coder_programs(mesh)
    return _MESH_PROGRAMS[mesh]


def _active_programs() -> Dict[str, Any]:
    from repro.sharding import api as shard_api
    return coder_programs(shard_api.current_lane_mesh())


# ---------------------------------------------------------------------------
# vectorized Repeat nodes (the fused leaves of a lowered tree)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _GridRepeat(Codec):
    """A ``Repeat`` of max-entropy-grid leaves, fused.

    ``kind``: "uniform" (mu/sigma unused), "gaussian" (mu, sigma) or
    "logistic" (mu carries location, sigma the scale); parameters are
    [n, lanes] in natural position order. Bit-exact with the
    per-position ``Repeat``: push flips to the LIFO order (positions
    n-1..0), pop streams positions in natural order. The starts/freqs
    CDF chain is the canonical fusion-stable form, so both directions
    run as one jitted program each (starts evaluated in-jit - see
    ``_push_grid_body``) on the backend ``kernels.dispatch`` resolves
    per call.
    """

    kind: str
    mu: Optional[jnp.ndarray]
    sigma: Optional[jnp.ndarray]
    n: int
    bits: int
    precision: int
    out_dtype: Any = jnp.int32
    donate: bool = True

    @spans.spanned(spans.CODER)
    def push(self, stack: ans.ANSStack, x: jnp.ndarray) -> ans.ANSStack:
        idx = x.astype(jnp.int32).T                       # [n, lanes]
        mu = self.mu if self.mu is not None else jnp.zeros(())
        sigma = self.sigma if self.sigma is not None else jnp.zeros(())
        d = dispatch.resolve("push_many", lanes=stack.lanes)
        return _active_programs()["push_grid"][self.donate](
            stack, idx, mu, sigma, kind=self.kind, bits=self.bits,
            precision=self.precision, backend=d)

    @spans.spanned(spans.CODER)
    def pop(self, stack: ans.ANSStack):
        mu = self.mu if self.mu is not None else jnp.zeros(())
        sigma = self.sigma if self.sigma is not None else jnp.zeros(())
        d = dispatch.resolve("pop_many_grid", lanes=stack.lanes)
        stack, syms = _active_programs()["pop_grid"][self.donate](
            stack, mu=mu, sigma=sigma, kind=self.kind, steps=self.n,
            lat_bits=self.bits, precision=self.precision, backend=d)
        return stack, syms.T.astype(self.out_dtype)


@dataclasses.dataclass(frozen=True)
class _TableRepeat(Codec):
    """A ``Repeat`` of table-coded leaves, fused.

    ``tables``: uint32[n, lanes, A+1] per-position cumulative starts in
    natural order (built eagerly at lowering time - canonical bits);
    one dynamic multi-step program call each way, starts gathered
    in-jit (integer gather - see ``_push_table_body``).
    """

    tables: jnp.ndarray
    precision: int
    out_dtype: Any = jnp.int32
    donate: bool = True

    @spans.spanned(spans.CODER)
    def push(self, stack: ans.ANSStack, x: jnp.ndarray) -> ans.ANSStack:
        symT = x.astype(jnp.int32).T                      # [n, lanes]
        d = dispatch.resolve("push_many_table", lanes=stack.lanes,
                             table_size=self.tables.shape[-1] - 1)
        return _active_programs()["push_table"][self.donate](
            stack, self.tables, symT, precision=self.precision,
            backend=d)

    @spans.spanned(spans.CODER)
    def pop(self, stack: ans.ANSStack):
        d = dispatch.resolve("pop_many_dyn", lanes=stack.lanes,
                             table_size=self.tables.shape[-1] - 1)
        stack, syms = _active_programs()["pop_dyn"][self.donate](
            stack, self.tables, precision=self.precision, backend=d)
        return stack, syms.T.astype(self.out_dtype)


# ---------------------------------------------------------------------------
# fused fixed-point programs (model forward INSIDE the jit)
# ---------------------------------------------------------------------------
# When a BBANS/BitSwap tree's function-valued children are
# ``quantize.FixedPointFn`` markers, the whole combinator schedule -
# quantized network forward, CDF bucketize, ANS renorm - is traced into
# ONE jitted program per direction. The model math is integer/LUT
# (exact in any fusion context, see codecs/quantize.py) and the
# Gaussian CDF chain is the same canonical form the kernels already
# evaluate inside jit, so wire bytes are identical to the interpreted
# (eager) twin of the same quantized codec. The eager-float hop per
# Repeat step - the dominant cost of the lazy BBANS lowering below -
# disappears entirely.

def _traced_push_uniform(stack: ans.ANSStack, idxT: jnp.ndarray,
                         bits: int, precision: int,
                         backend=None) -> ans.ANSStack:
    shift = precision - bits
    start = idxT.astype(jnp.uint32) << shift
    freq = jnp.full_like(start, jnp.uint32(1 << shift))
    return ans_ops.push_many(stack, start[::-1], freq[::-1],
                             precision=precision, backend=backend)


def _traced_push_gaussian(stack: ans.ANSStack, idxT: jnp.ndarray,
                          muT: jnp.ndarray, sigmaT: jnp.ndarray,
                          bits: int, precision: int,
                          backend=None) -> ans.ANSStack:
    f = discretize.posterior_starts_fn(muT, sigmaT, bits, precision)
    start = f(idxT)
    freq = f(idxT + 1) - start
    return ans_ops.push_many(stack, start[::-1], freq[::-1],
                             precision=precision, backend=backend)


def _fp_push(stack: ans.ANSStack, fx: "Q.FixedPointFn", ctx: Any,
             sym: jnp.ndarray, backend=None) -> ans.ANSStack:
    """Push ``sym`` under the codec ``fx`` parameterizes by ``ctx``."""
    flat = sym.reshape(sym.shape[0], -1).astype(jnp.int32)
    if fx.family == "gaussian":
        mu, sigma = fx.params(ctx)
        return _traced_push_gaussian(stack, flat.T, mu.T, sigma.T,
                                     fx.bits, fx.precision, backend)
    f1 = fx.params(ctx).T.astype(jnp.uint32)          # [n, lanes]
    total = jnp.uint32(1 << fx.precision)
    f0 = total - f1
    is1 = flat.T.astype(bool)
    start = jnp.where(is1, f0, jnp.uint32(0))
    freq = jnp.where(is1, f1, f0)
    return ans_ops.push_many(stack, start[::-1], freq[::-1],
                             precision=fx.precision, backend=backend)


def _fp_pop(stack: ans.ANSStack, fx: "Q.FixedPointFn",
            ctx: Any, backend=None) -> tuple:
    """Pop a symbol under the codec ``fx`` parameterizes by ``ctx``."""
    if fx.family == "gaussian":
        mu, sigma = fx.params(ctx)
        stack, symT = ans_ops.pop_many_grid(
            stack, "gaussian", mu.T, sigma.T, fx.n, fx.bits,
            precision=fx.precision, backend=backend)
    else:
        f1 = fx.params(ctx).T.astype(jnp.uint32)      # [n, lanes]
        total = jnp.uint32(1 << fx.precision)
        tables = jnp.stack(
            [jnp.zeros_like(f1), total - f1, jnp.full_like(f1, total)],
            axis=-1)
        stack, symT = ans_ops.pop_many_dyn(stack, tables,
                                           precision=fx.precision,
                                           backend=backend)
    sym = symT.T
    if fx.shape:
        sym = sym.reshape((sym.shape[0],) + tuple(fx.shape))
    return stack, sym


class _FusedBBANS(Codec):
    """``BBANS`` with FixedPointFn children: one jit per direction.

    The push/pop bodies replay ``combinators.BBANS``'s exact schedule
    with the quantized model forward traced in-line and every
    multi-symbol leg on the fused kernels. ``push_body``/``pop_body``
    are the untraced schedules, reused by ``_FusedChained``'s scan.
    """

    def __init__(self, prior_bits: int, prior_precision: int,
                 posterior: "Q.FixedPointFn", likelihood: "Q.FixedPointFn",
                 donate: bool = True):
        n_lat = posterior.n

        def push_body(stack, s, backend=None):
            mu, sigma = posterior.params(s)
            stack, yT = ans_ops.pop_many_grid(
                stack, "gaussian", mu.T, sigma.T, n_lat, posterior.bits,
                precision=posterior.precision, backend=backend)
            stack = _fp_push(stack, likelihood, yT.T, s, backend)
            return _traced_push_uniform(stack, yT, prior_bits,
                                        prior_precision, backend)

        def pop_body(stack, backend=None):
            z = jnp.zeros(())
            stack, yT = ans_ops.pop_many_grid(
                stack, "uniform", z, z, n_lat, prior_bits,
                precision=prior_precision, backend=backend)
            stack, s = _fp_pop(stack, likelihood, yT.T, backend)
            mu, sigma = posterior.params(s)
            stack = _traced_push_gaussian(stack, yT, mu.T, sigma.T,
                                          posterior.bits,
                                          posterior.precision, backend)
            return stack, s

        self.push_body, self.pop_body = push_body, pop_body
        dn = (0,) if donate else ()
        self._push = jax.jit(push_body, donate_argnums=dn,
                             static_argnames=("backend",))
        self._pop = jax.jit(pop_body, donate_argnums=dn,
                            static_argnames=("backend",))

    @spans.spanned(spans.CODER)
    def push(self, stack: ans.ANSStack, s: Any) -> ans.ANSStack:
        return self._push(stack, s,
                          backend=dispatch.resolve("push_many",
                                                   lanes=stack.lanes))

    @spans.spanned(spans.CODER)
    def pop(self, stack: ans.ANSStack):
        return self._pop(stack,
                         backend=dispatch.resolve("pop_many_grid",
                                                  lanes=stack.lanes))


class _FusedBitSwap(Codec):
    """``BitSwap`` with FixedPointFn layers: one jit per direction."""

    def __init__(self, prior_bits: int, prior_precision: int, n_lat: int,
                 layers: tuple, donate: bool = True):
        def push_body(stack, s, backend=None):
            ctx = s
            for post_f, lik_f in layers:
                mu, sigma = post_f.params(ctx)
                stack, zT = ans_ops.pop_many_grid(
                    stack, "gaussian", mu.T, sigma.T, post_f.n,
                    post_f.bits, precision=post_f.precision,
                    backend=backend)
                stack = _fp_push(stack, lik_f, zT.T, ctx, backend)
                ctx = zT.T
            return _traced_push_uniform(stack, ctx.T, prior_bits,
                                        prior_precision, backend)

        def pop_body(stack, backend=None):
            zz = jnp.zeros(())
            stack, zT = ans_ops.pop_many_grid(
                stack, "uniform", zz, zz, n_lat, prior_bits,
                precision=prior_precision, backend=backend)
            z = zT.T
            for post_f, lik_f in reversed(layers):
                stack, ctx = _fp_pop(stack, lik_f, z, backend)
                mu, sigma = post_f.params(ctx)
                stack = _traced_push_gaussian(stack, z.T, mu.T, sigma.T,
                                              post_f.bits,
                                              post_f.precision, backend)
                z = ctx
            return stack, z

        self.push_body, self.pop_body = push_body, pop_body
        dn = (0,) if donate else ()
        self._push = jax.jit(push_body, donate_argnums=dn,
                             static_argnames=("backend",))
        self._pop = jax.jit(pop_body, donate_argnums=dn,
                            static_argnames=("backend",))

    @spans.spanned(spans.CODER)
    def push(self, stack: ans.ANSStack, s: Any) -> ans.ANSStack:
        return self._push(stack, s,
                          backend=dispatch.resolve("push_many",
                                                   lanes=stack.lanes))

    @spans.spanned(spans.CODER)
    def pop(self, stack: ans.ANSStack):
        return self._pop(stack,
                         backend=dispatch.resolve("pop_many_grid",
                                                  lanes=stack.lanes))


class _FusedChained(Codec):
    """``Chained`` over a fused fixed-point inner: the whole chain is a
    ``lax.scan`` of the inner's schedule - one jit for ALL datapoints.

    Safe here (and only here): the scan body is integer/LUT model math
    plus the canonical CDF chain, both bit-stable in any fusion
    context, so the per-datapoint bytes match the Python chain loop.
    """

    def __init__(self, inner: Codec, n: int, donate: bool = True):
        self.n = n
        inner_push, inner_pop = inner.push_body, inner.pop_body

        def push_body(stack, data, backend=None):
            def body(st, s):
                return inner_push(st, s, backend), None

            stack, _ = jax.lax.scan(body, stack, data)
            return stack

        def pop_body(stack, backend=None):
            def body(st, _):
                st, s = inner_pop(st, backend)
                return st, s

            stack, rev = jax.lax.scan(body, stack, None, length=n)
            return stack, jax.tree_util.tree_map(
                lambda x: jnp.flip(x, axis=0), rev)

        dn = (0,) if donate else ()
        self._push = jax.jit(push_body, donate_argnums=dn,
                             static_argnames=("backend",))
        self._pop = jax.jit(pop_body, donate_argnums=dn,
                            static_argnames=("backend",))

    @spans.spanned(spans.CODER)
    def push(self, stack: ans.ANSStack, data: Any) -> ans.ANSStack:
        for leaf in jax.tree_util.tree_leaves(data):
            if leaf.shape[0] != self.n:
                raise ValueError(
                    f"Chained(n={self.n}): data leading axis is "
                    f"{leaf.shape[0]} - a mismatch would silently code "
                    "the wrong number of datapoints")
        return self._push(stack, data,
                          backend=dispatch.resolve("push_many",
                                                   lanes=stack.lanes))

    @spans.spanned(spans.CODER)
    def pop(self, stack: ans.ANSStack):
        return self._pop(stack,
                         backend=dispatch.resolve("pop_many_grid",
                                                  lanes=stack.lanes))


def _uniform_prior_spec(prior: Codec, n_lat: int, donate: bool):
    """Lower a BBANS/BitSwap prior; accept only the uniform grid shape
    the fused schedules hard-code. Returns (bits, precision) or None."""
    if not isinstance(prior, C.Repeat):
        return None
    low = _lower_repeat(prior, donate)
    if not (isinstance(low, _GridRepeat) and low.kind == "uniform"
            and low.n == n_lat):
        return None
    return low.bits, low.precision


def _lower_fused_bbans(codec: C.BBANS, donate: bool) -> Optional[Codec]:
    post, lik = codec.posterior, codec.likelihood
    if not (isinstance(post, Q.FixedPointFn)
            and isinstance(lik, Q.FixedPointFn)):
        return None
    if post.family != "gaussian":
        return None
    spec = _uniform_prior_spec(codec.prior, post.n, donate)
    if spec is None:
        return None
    return _FusedBBANS(spec[0], spec[1], post, lik, donate)


def _lower_fused_bitswap(codec: C.BitSwap, donate: bool) -> Optional[Codec]:
    layers = codec.layers
    if not layers or not all(
            isinstance(p, Q.FixedPointFn) and isinstance(lk, Q.FixedPointFn)
            for p, lk in layers):
        return None
    if any(p.family != "gaussian" for p, _ in layers):
        return None
    n_lat = layers[-1][0].n
    spec = _uniform_prior_spec(codec.prior, n_lat, donate)
    if spec is None:
        return None
    return _FusedBitSwap(spec[0], spec[1], n_lat, layers, donate)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def _same(vals) -> bool:
    return all(v == vals[0] for v in vals[1:])


#: leaf family -> (array param fields, static fields). Order matters:
#: most-derived classes first (isinstance is used, so e.g. the HVAE's
#: KernelDiscretizedGaussian lowers as a Gaussian).
_FAMILIES = (
    (L.Uniform, (), ("bits", "precision")),
    (L.DiscretizedGaussian, ("mu", "sigma"), ("bits", "precision")),
    (L.DiscretizedLogistic, ("mu", "scale"), ("bits", "precision")),
    (Bernoulli, ("logits",), ("precision",)),
    (BetaBinomial, ("alpha", "beta"), ("n", "precision")),
    (Categorical, ("logits",), ("precision",)),
)


def _statics(leaf, names) -> tuple:
    return tuple(getattr(leaf, s) for s in names)


def _probe_params(rep: C.Repeat, leaf0, fields, statics):
    """Stack the per-position leaf parameters to [n, lanes, ...].

    Fast path: call ``codec_fn`` ONCE with ``arange(n)`` - elementwise
    closures (everything in this repo: ``mu[:, d]``-style slicing of a
    [lanes, n, ...] parent) then gather the whole parameter grid in one
    op, which is an exact copy in any compilation context. The result
    is spot-validated against eagerly probed positions {0, n//2, n-1};
    any surprise (shape, type, static fields, values) falls back to
    probing all ``n`` positions one by one - always correct, just O(n)
    dispatches.
    """
    n = rep.n
    vec = None
    try:
        vec = rep.codec_fn(jnp.arange(n, dtype=jnp.int32))
    except Exception:
        vec = None
    if vec is not None and type(vec) is type(leaf0) \
            and _statics(vec, statics) == _statics(leaf0, statics):
        out = []
        for name in fields:
            s0 = jnp.shape(getattr(leaf0, name))
            vv = getattr(vec, name)
            if jnp.shape(vv) != s0[:1] + (n,) + s0[1:]:
                out = None
                break
            out.append(jnp.moveaxis(jnp.asarray(vv), 1, 0))
        if out is not None:
            for d in sorted({0, n // 2, n - 1}):
                lf = rep.codec_fn(d)
                if type(lf) is not type(leaf0) or \
                        _statics(lf, statics) != _statics(leaf0, statics):
                    out = None
                    break
                same = (bool(spans.host_read(
                    jnp.array_equal(arr[d], getattr(lf, nm)),
                    "compile.probe")) for nm, arr in zip(fields, out))
                if not all(same):
                    out = None
                    break
            if out is not None:
                return out
    # Slow path: probe every position (heterogeneity checks included).
    leaves = [rep.codec_fn(d) for d in range(n)]
    if not all(type(lf) is type(leaf0) for lf in leaves):
        return None
    if not _same([_statics(lf, statics) for lf in leaves]):
        return None
    return [jnp.stack([jnp.asarray(getattr(lf, nm)) for lf in leaves])
            for nm in fields]


#: the faults ``_table_faults`` flags, in the order of its verdict
_TABLE_FAULTS = ("freq-sum", "starts-monotone", "freq-zero")


@functools.partial(jax.jit, static_argnames=("precision",))
def _table_faults(tables: jnp.ndarray, *, precision: int) -> jnp.ndarray:
    """bool[3] verdict over every table: an end off [0, 2^precision],
    a decreasing start, a zero-width symbol (``_TABLE_FAULTS``).
    Comparisons only, in the tables' own dtype: exact at any precision,
    and on a lane-sharded table one replicated verdict."""
    lo, hi = tables[..., :-1], tables[..., 1:]
    return jnp.stack([
        jnp.any((tables[..., 0] != 0) | (tables[..., -1] != 1 << precision)),
        jnp.any(hi < lo),
        jnp.any(hi <= lo)])


def _validate_tables(tables: jnp.ndarray, precision: int,
                     what: str) -> None:
    """Frequency-soundness gate on lowered fixed-point tables: exact
    span, monotone starts, no zero-mass symbol. Runs once per lowering
    (the tables are already concrete), so a broken table fails here
    naming the subtree instead of as a hex mismatch at decode time.

    The device checks the tables and only its 3-byte verdict is read;
    a flagged table is then read whole, and ``_check_tables_host``
    names the fault."""
    flags = spans.host_read(_table_faults(tables, precision=precision),
                            "compile.tables")
    if flags.any():
        _check_tables_host(spans.host_read(tables, "compile.tables"),
                           precision, what)
        raise ValueError(
            f"codecs.compile: contract violation "
            f"({_TABLE_FAULTS[int(np.argmax(flags))]}) in {what}")


def _check_tables_host(tables: np.ndarray, precision: int,
                       what: str) -> None:
    """The host's check of tables read to it: raises on the first
    fault, with how many tables break the span and the first one."""
    t = tables.astype(np.int64)
    total = 1 << precision
    bad = (t[..., 0] != 0) | (t[..., -1] != total)
    if bad.any():
        first = tuple(int(i) for i in np.argwhere(bad)[0])
        raise ValueError(
            f"codecs.compile: contract violation (freq-sum) in {what}: "
            f"{int(bad.sum())} of {bad.size} tables do not span exactly "
            f"[0, 2^{precision}]; the first, at {first}, spans "
            f"[{int(t[first][0])}, {int(t[first][-1])}]")
    d = np.diff(t, axis=-1)
    if (d < 0).any():
        raise ValueError(
            f"codecs.compile: contract violation (starts-monotone) in "
            f"{what}: cumulative starts decrease")
    if (d < 1).any():
        raise ValueError(
            f"codecs.compile: contract violation (freq-zero) in {what}: "
            "a symbol has zero frequency and would decode to a "
            "neighbour silently")


def _validate_grid_params(arr: jnp.ndarray, name: str, what: str,
                          positive: bool = False) -> None:
    a = spans.host_read(arr, "compile.grid_params")
    if not np.isfinite(a).all():
        raise ValueError(
            f"codecs.compile: contract violation (starts-monotone) in "
            f"{what}: non-finite {name}")
    if positive and (a <= 0).any():
        raise ValueError(
            f"codecs.compile: contract violation (starts-monotone) in "
            f"{what}: {name} must be strictly positive (a non-positive "
            "scale flips the CDF and breaks the decode bisection)")


@spans.spanned(spans.LOWER)
def _lower_repeat(rep: C.Repeat, donate: bool) -> Optional[Codec]:
    """Probe a ``Repeat``'s positions; fuse when the leaf family allows.

    Returns ``None`` when the body is unrecognized (heterogeneous,
    closure-opaque, degenerate) - the caller falls back to the
    interpreted ``Repeat``, which is always correct.
    """
    if rep.n <= 0:
        return None
    try:
        leaf0 = rep.codec_fn(0)
    except Exception:
        return None
    family = next(((cls, fields, statics)
                   for cls, fields, statics in _FAMILIES
                   if isinstance(leaf0, cls)), None)
    if family is None:
        return None
    cls, fields, statics = family
    try:
        params = _probe_params(rep, leaf0, fields, statics)
    except Exception:
        params = None
    if params is None:
        return None

    if cls is L.Uniform:
        return _GridRepeat("uniform", None, None, rep.n, leaf0.bits,
                           leaf0.precision, rep.out_dtype, donate)
    if cls is L.DiscretizedGaussian:
        mu, sigma = (p.astype(jnp.float32) for p in params)
        what = f"Repeat[DiscretizedGaussian, n={rep.n}]"
        _validate_grid_params(mu, "mu", what)
        _validate_grid_params(sigma, "sigma", what, positive=True)
        return _GridRepeat("gaussian", mu, sigma, rep.n, leaf0.bits,
                           leaf0.precision, rep.out_dtype, donate)
    if cls is L.DiscretizedLogistic:
        mu, scale = (p.astype(jnp.float32) for p in params)
        what = f"Repeat[DiscretizedLogistic, n={rep.n}]"
        _validate_grid_params(mu, "mu", what)
        _validate_grid_params(scale, "scale", what, positive=True)
        return _GridRepeat("logistic", mu, scale, rep.n, leaf0.bits,
                           leaf0.precision, rep.out_dtype, donate)

    # Table families: the fixed-point tables are built in ONE vectorized
    # evaluation - the same elementwise arithmetic as the per-position
    # leaf (`_freq1`/`_table`) broadcast over the position axis, so the
    # bits are identical (eager elementwise ops are shape-independent).
    if cls is Bernoulli:
        total = 1 << leaf0.precision
        p = jax.nn.sigmoid(params[0].astype(jnp.float32))  # [n, lanes]
        f1 = jnp.round(p * (total - 2)).astype(jnp.uint32) + 1
        tables = jnp.stack(
            [jnp.zeros_like(f1), jnp.uint32(total) - f1,
             jnp.full_like(f1, jnp.uint32(total))], axis=-1)
        _validate_tables(tables, leaf0.precision,
                         f"Repeat[Bernoulli, n={rep.n}]")
        return _TableRepeat(tables, leaf0.precision, rep.out_dtype,
                            donate)
    if cls is BetaBinomial:
        alpha, beta = params
        ks = jnp.arange(leaf0.n + 1, dtype=jnp.float32)
        logp = beta_binomial_log_pmf(
            ks[None, None, :], leaf0.n,
            alpha[..., None].astype(jnp.float32),
            beta[..., None].astype(jnp.float32))
        tables = ans.probs_to_starts(_stable_softmax(logp),
                                     leaf0.precision)
        _validate_tables(tables, leaf0.precision,
                         f"Repeat[BetaBinomial, n={rep.n}]")
        return _TableRepeat(tables, leaf0.precision, rep.out_dtype,
                            donate)
    if cls is Categorical:
        tables = ans.probs_to_starts(
            _stable_softmax(params[0].astype(jnp.float32)),
            leaf0.precision)
        _validate_tables(tables, leaf0.precision,
                         f"Repeat[Categorical, n={rep.n}]")
        return _TableRepeat(tables, leaf0.precision, rep.out_dtype,
                            donate)
    return None


#: type -> (codec, recurse) -> lowered codec. Extension point for
#: combinators defined outside this package (``stream.BlockChain``
#: registers itself at import time).
_LOWERINGS: Dict[Type, Callable[[Any, Callable], Codec]] = {}


def register_lowering(cls: Type,
                      fn: Callable[[Any, Callable], Codec]) -> None:
    """Register a structural lowering for an external combinator class.

    ``fn(codec, recurse)`` must return a bit-exact rewrite of ``codec``
    (typically the same class over ``recurse``-lowered children).
    """
    _LOWERINGS[cls] = fn


def _lower(codec: Codec, donate: bool = True) -> Codec:
    """Structurally rewrite a codec tree into its fused form."""
    rec = lambda c: _lower(c, donate)
    fn = _LOWERINGS.get(type(codec))
    if fn is not None:
        return fn(codec, rec)
    if isinstance(codec, C.Repeat):
        return _lower_repeat(codec, donate) or codec
    if isinstance(codec, C.Shaped):
        return C.Shaped(rec(codec.inner), codec.shape)
    if isinstance(codec, C.Serial):
        return C.Serial([rec(c) for c in codec.codecs])
    if isinstance(codec, C.TreeCodec):
        leaves, treedef = jax.tree_util.tree_flatten(
            codec.tree, is_leaf=lambda c: isinstance(c, Codec))
        return C.TreeCodec(treedef.unflatten([rec(c) for c in leaves]))
    if isinstance(codec, C.Chained):
        inner_l = rec(codec.inner)
        if isinstance(inner_l, (_FusedBBANS, _FusedBitSwap)):
            # Fixed-point inner: the chain body is bit-stable under
            # fusion, so the whole chain scans inside one program.
            return _FusedChained(inner_l, codec.n, donate)
        # scan=False: a lax.scan would trace the float evaluations into
        # one fused program, breaking the canonical-eager contract; the
        # Python chain loop is per-datapoint (cheap), not per-symbol.
        return C.Chained(inner_l, codec.n, scan=False)
    if isinstance(codec, C.BBANS):
        fused = _lower_fused_bbans(codec, donate)
        if fused is not None:
            return fused
        lik, post = codec.likelihood, codec.posterior
        return C.BBANS(prior=rec(codec.prior),
                       likelihood=lambda y: rec(lik(y)),
                       posterior=lambda s: rec(post(s)))
    if isinstance(codec, C.BitSwap):
        fused = _lower_fused_bitswap(codec, donate)
        if fused is not None:
            return fused
        layers = tuple(
            (lambda ctx, _p=p: rec(_p(ctx)),
             lambda z, _l=lk: rec(_l(z)))
            for p, lk in codec.layers)
        return C.BitSwap(prior=rec(codec.prior), layers=layers)
    return codec


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------

def _consult_tuning(codec: Codec) -> None:
    """Walk a lowered tree and warm the kernel tuning cache for its
    fused nodes. Only active under ``REPRO_AUTOTUNE=1`` (measured
    autotuning at lowering time is opt-in; without it, cache hits from
    previous runs still apply via ``dispatch.resolve``)."""
    if not os.environ.get("REPRO_AUTOTUNE"):
        return
    from repro.kernels import tuning

    def walk(c: Any) -> None:
        if isinstance(c, _GridRepeat):
            lanes = c.mu.shape[1] if c.mu is not None \
                and jnp.ndim(c.mu) == 2 else None
            tuning.ensure("push_many", lanes=lanes, steps=c.n,
                          lat_bits=c.bits, precision=c.precision)
            tuning.ensure("pop_many_grid", lanes=lanes, steps=c.n,
                          lat_bits=c.bits, precision=c.precision)
        elif isinstance(c, _TableRepeat):
            lanes, tsize = c.tables.shape[1], c.tables.shape[2] - 1
            tuning.ensure("push_many_table", lanes=lanes,
                          table_size=tsize, steps=c.tables.shape[0],
                          precision=c.precision)
            tuning.ensure("pop_many_dyn", lanes=lanes, table_size=tsize,
                          steps=c.tables.shape[0], precision=c.precision)
        elif isinstance(c, C.Shaped):
            walk(c.inner)
        elif isinstance(c, C.Serial):
            for child in c.codecs:
                walk(child)
        elif isinstance(c, C.TreeCodec):
            for child in jax.tree_util.tree_leaves(
                    c.tree, is_leaf=lambda x: isinstance(x, Codec)):
                walk(child)
        elif isinstance(c, C.Chained):
            walk(c.inner)

    walk(codec)


class CompiledCodec(Codec):
    """A codec lowered into fused kernel-backed execution.

    Drop-in for the source codec anywhere a ``Codec`` is accepted
    (container, stream, engine): same wire bytes, a handful of jitted
    integer coder programs per direction instead of one host dispatch
    per symbol. The ``ANSStack`` flowing through those programs is
    donated by default, so coder state updates in place on backends
    that support donation.

    Note the donation contract: after ``prog.push(stack, x)`` the
    *input* stack's buffers may be invalid - callers must use the
    returned stack (every driver in this repo already does; tests that
    deliberately reuse a stack pass ``donate=False``).
    """

    def __init__(self, codec: Codec, *, donate: bool = True):
        self.source = codec
        self.lowered = _lower(codec, donate)
        _consult_tuning(self.lowered)

    def push(self, stack: ans.ANSStack, x: Any) -> ans.ANSStack:
        return self.lowered.push(stack, x)

    def pop(self, stack: ans.ANSStack):
        return self.lowered.pop(stack)


def compile(codec: Codec, *, donate: bool = True,
            verify: bool = False) -> CompiledCodec:
    """Compile a codec tree into a fused kernel-backed program.

    Returns a ``CompiledCodec`` that codes byte-identically to
    ``codec`` (compiling an already-compiled codec is a no-op).
    Lowered fixed-point tables are always validated for frequency
    soundness (a broken table raises ``ValueError`` here, naming the
    subtree); ``verify=True`` additionally runs the full
    ``repro.analysis`` contract verifier over the source tree and
    raises ``analysis.ContractViolation`` on any error finding.

    Example::

        prog = codecs.compile(codecs.Repeat(
            lambda d: codecs.Uniform(8), 64))
        stack = prog.push(stack, x)        # ONE fused kernel call
    """
    if isinstance(codec, CompiledCodec):
        return codec
    if verify:
        from repro.analysis import check_codec   # lazy: avoid cycle
        check_codec(codec, context="codecs.compile")
    return CompiledCodec(codec, donate=donate)

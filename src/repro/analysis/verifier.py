"""The codec-tree contract verifier (see package docstring).

Two passes over a ``Codec`` tree, both driven by a scratch stack seeded
with deterministic clean bits - no user data is coded:

  1. **Inverse probe**: ``pop`` the whole tree off a fresh stack, push
     the decoded value back, and require the stack to come back
     bit-identical (head, chunk buffer, and depth). This is the paper's
     App.-C contract checked end to end (rule ``inverse-probe``).
  2. **Collection walk**: a decode-ordered traversal of the combinator
     structure. Function children (``BBANS`` likelihood/posterior,
     ``BitSwap`` layers) are materialized by popping representative
     values from the scratch stack, exactly as a decode would; every
     leaf then gets
       - frequency-table soundness checks (``freq-sum``, ``freq-zero``,
         ``starts-monotone``),
       - a mirror probe comparing the (start, freq, precision) events
         of one pop against the push that inverts it
         (``push-pop-mirror``),
       - jaxpr rules over its traced push/pop programs (``float-leak``,
         ``div-shared``, ``ndtri-coder``),
     plus the structural PR-4 rules (``scan-chain``, ``edge-cache``)
     and a worst-case bits-per-datapoint bound (``capacity-bound``).

Opaque leaves - ``FnCodec``, ``core.lm_codec.TokenStream``, any class
marking itself ``__analysis_opaque__ = True`` - are driver codecs whose
float evaluation happens inside jitted network steps they manage
themselves; they are probed for inversion only (the jaxpr rules would
false-positive on network internals like softmax divisions). Unknown
``Codec`` subclasses are treated the same but noted in the report.
"""

from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ans, discretize
from repro.core.codec import Codec, FnCodec
from repro.core.distributions import (Bernoulli, BetaBinomial, Categorical,
                                      FactoredCategorical)
from repro.codecs import combinators as C
from repro.codecs import leaves as L
from repro.codecs.container import fresh_stack


# ---------------------------------------------------------------------------
# findings and reports
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation (or warning/note) at a tree path.

    ``rule`` is a key of ``analysis.RULES``; ``path`` names the
    offending subtree (e.g. ``codec.likelihood(y).codec_fn(3)``);
    ``hint`` says how to fix it.
    """

    rule: str
    severity: str          # "error" | "warning" | "info"
    path: str
    message: str
    hint: str = ""

    def __str__(self) -> str:
        tail = f"\n      hint: {self.hint}" if self.hint else ""
        return (f"[{self.severity}] {self.rule} at {self.path}: "
                f"{self.message}{tail}")


@dataclasses.dataclass(frozen=True)
class Report:
    """The outcome of ``verify_codec``: findings plus context.

    ``findings`` holds errors and warnings (the things that gate);
    ``notes`` holds info-level observations (opaque leaves probed but
    not traced). ``bits_bound`` is the worst-case bits one datapoint
    can push per lane (``None`` when the tree contains opaque leaves
    whose cost is unknowable statically).
    """

    context: str
    findings: Tuple[Finding, ...]
    notes: Tuple[Finding, ...] = ()
    bits_bound: Optional[float] = None

    @property
    def errors(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == "error")

    @property
    def warnings(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == "warning")

    @property
    def ok(self) -> bool:
        """True when no *errors* were found (warnings don't gate)."""
        return not self.errors

    def __str__(self) -> str:
        if not self.findings and not self.notes:
            return f"{self.context}: clean"
        lines = [f"{self.context}: {len(self.errors)} error(s), "
                 f"{len(self.warnings)} warning(s)"]
        lines += [f"  {f}" for f in self.findings]
        lines += [f"  {n}" for n in self.notes]
        return "\n".join(lines)


class ContractViolation(RuntimeError):
    """Raised by ``check_codec`` when verification finds errors; the
    full ``Report`` rides along as ``.report``."""

    def __init__(self, report: Report):
        self.report = report
        super().__init__(str(report))


# ---------------------------------------------------------------------------
# walk context
# ---------------------------------------------------------------------------

class _Ctx:
    def __init__(self) -> None:
        self.findings: List[Finding] = []
        self.notes: List[Finding] = []
        self.bound = 0.0
        self.bound_exact = True

    def error(self, rule: str, path: str, msg: str, hint: str = "") -> None:
        self.findings.append(Finding(rule, "error", path, msg, hint))

    def warn(self, rule: str, path: str, msg: str, hint: str = "") -> None:
        self.findings.append(Finding(rule, "warning", path, msg, hint))

    def note(self, rule: str, path: str, msg: str, hint: str = "") -> None:
        self.notes.append(Finding(rule, "info", path, msg, hint))


def _unwrap(codec: Codec) -> Codec:
    """Analyze a ``CompiledCodec`` through its source tree: the lowering
    is bit-exact by construction (and separately validated at lowering
    time), and the source tree is the form the rules understand."""
    src = getattr(codec, "source", None)
    return src if isinstance(src, Codec) else codec


def _stacks_equal(a: ans.ANSStack, b: ans.ANSStack) -> Optional[str]:
    """None when coder state matches bit-for-bit, else a description."""
    ah, bh = np.asarray(a.head), np.asarray(b.head)
    ap, bp = np.asarray(a.ptr), np.asarray(b.ptr)
    ab, bb = np.asarray(a.buf), np.asarray(b.buf)
    if (ah != bh).any():
        lanes = np.nonzero(ah != bh)[0][:4].tolist()
        return f"head differs on lanes {lanes}"
    if (ap != bp).any():
        lanes = np.nonzero(ap != bp)[0][:4].tolist()
        return f"stack depth differs on lanes {lanes}"
    # Only chunks below ptr are live; slots above it are dead scratch
    # that interleaved bits-back pushes legitimately leave behind.
    live = np.arange(ab.shape[1])[None, :] < ap[:, None]
    if ((ab != bb) & live).any():
        lane, col = (int(x[0]) for x in np.nonzero((ab != bb) & live))
        return f"chunk buffer differs first at lane {lane}, slot {col}"
    return None


# ---------------------------------------------------------------------------
# (start, freq) event recording - the push/pop mirror check
# ---------------------------------------------------------------------------

class _Recorder:
    def __init__(self) -> None:
        self.events: List[Tuple[str, np.ndarray, np.ndarray, int]] = []


@contextmanager
def _recording(rec: _Recorder):
    """Temporarily interpose on ``ans.push``/``ans.pop_update`` to log
    every (start, freq, precision) triple the tree hands the coder.
    Works because every caller in the repo resolves them through the
    module attribute at call time."""
    real_push, real_pop = ans.push, ans.pop_update

    def push(stack, start, freq, precision=ans.DEFAULT_PRECISION):
        rec.events.append(("push", np.asarray(start), np.asarray(freq),
                           precision))
        return real_push(stack, start, freq, precision)

    def pop_update(stack, start, freq, precision=ans.DEFAULT_PRECISION):
        rec.events.append(("pop", np.asarray(start), np.asarray(freq),
                           precision))
        return real_pop(stack, start, freq, precision)

    ans.push, ans.pop_update = push, pop_update
    try:
        yield rec
    finally:
        ans.push, ans.pop_update = real_push, real_pop


# ---------------------------------------------------------------------------
# jaxpr rules: float-leak, div-shared, ndtri-coder
# ---------------------------------------------------------------------------

_BARRIERS = frozenset({"floor", "ceil", "round", "round_nearest_even",
                       "sign"})
# Value-preserving ops the barrier search looks through.
_TRANSPARENT = frozenset({"broadcast_in_dim", "reshape", "squeeze",
                          "expand_dims", "transpose", "slice", "rev",
                          "copy", "gather", "dynamic_slice",
                          "concatenate", "pad", "select_n",
                          "convert_element_type", "stop_gradient"})
# Call-like wrappers (jnp.floor/round are jit-wrapped composites).
_WRAPPERS = frozenset({"jit", "closed_call", "core_call", "remat2",
                       "checkpoint", "custom_jvp_call",
                       "custom_vjp_call"})


def _inner_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr"):
        j = eqn.params.get(key)
        if j is not None:
            return getattr(j, "jaxpr", j)
    return None


def _feeds_barrier(var, jaxpr, defs, outer, depth=0) -> bool:
    """True when ``var``'s float value demonstrably passed through an
    explicit floor/round barrier (or is concrete: a literal, a jaxpr
    input, or a constvar). ``outer(i)`` re-runs the check on the
    enclosing frame's i-th call operand."""
    if _is_literal(var) or depth > 32:
        return True
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    if dtype is not None and not jnp.issubdtype(dtype, jnp.floating):
        return True
    eqn = defs.get(var)
    if eqn is None:
        if outer is not None and var in jaxpr.invars:
            return outer(jaxpr.invars.index(var))
        return True   # top-level input or constvar: concrete bits
    name = eqn.primitive.name
    if name in _BARRIERS:
        return True
    if name in _TRANSPARENT:
        return all(_feeds_barrier(v, jaxpr, defs, outer, depth + 1)
                   for v in eqn.invars)
    if name in _WRAPPERS:
        inner = _inner_jaxpr(eqn)
        if inner is None:
            return False
        try:
            idx = eqn.outvars.index(var)
            target = inner.outvars[idx]
        except (ValueError, IndexError):
            return False
        sub_defs = {}
        for e in inner.eqns:
            for ov in e.outvars:
                sub_defs[ov] = e

        def sub_outer(i, _eqn=eqn):
            if i >= len(_eqn.invars):
                return True
            return _feeds_barrier(_eqn.invars[i], jaxpr, defs, outer,
                                  depth + 1)

        return _feeds_barrier(target, inner, sub_defs, sub_outer,
                              depth + 1)
    return False


def _sub_jaxprs(params):
    out = []
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            j = getattr(item, "jaxpr", None)   # ClosedJaxpr -> Jaxpr
            if j is not None and hasattr(j, "eqns"):
                out.append(j)
            elif hasattr(item, "eqns"):        # bare Jaxpr
                out.append(item)
    return out


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _scan_jaxpr(jaxpr, ctx: _Ctx, path: str, seen_rules: set) -> None:
    defs = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            # Kernel boundary: bodies are checked at source level by
            # the AST lint (repro.analysis.lint), not here.
            continue
        if name == "erf_inv" and "ndtri-coder" not in seen_rules:
            seen_rules.add("ndtri-coder")
            ctx.error(
                "ndtri-coder", path,
                "ndtri (erf_inv) is evaluated inside a coder program - "
                "its float32 bits vary with the XLA fusion context, so "
                "encode and decode can disagree",
                "gather bucket geometry from the concrete "
                "core.discretize.edge_table/centre_table instead of "
                "recomputing ndtri inline")
        elif name == "div":
            out = eqn.outvars[0]
            dtype = getattr(getattr(out, "aval", None), "dtype", None)
            if (dtype is not None and jnp.issubdtype(dtype, jnp.floating)
                    and not _is_literal(eqn.invars[0])
                    and not _is_literal(eqn.invars[1])
                    and "div-shared" not in seen_rules):
                seen_rules.add("div-shared")
                ctx.error(
                    "div-shared", path,
                    "non-reciprocal float division in a coder program - "
                    "XLA rewrites shared-divisor divisions to "
                    "multiply-by-reciprocal in some fusion contexts and "
                    "not others, flipping fixed-point floors",
                    "write the canonical form x * (1.0 / d) so every "
                    "compilation context produces the same bits")
        elif name == "convert_element_type":
            src = eqn.invars[0]
            if _is_literal(src):
                continue
            src_dtype = getattr(getattr(src, "aval", None), "dtype", None)
            new_dtype = eqn.params.get("new_dtype")
            if (src_dtype is None or new_dtype is None
                    or not jnp.issubdtype(src_dtype, jnp.floating)
                    or not jnp.issubdtype(new_dtype, jnp.integer)):
                continue
            if not _feeds_barrier(src, jaxpr, defs, None) \
                    and "float-leak" not in seen_rules:
                seen_rules.add("float-leak")
                producer = defs.get(src)
                pname = producer.primitive.name if producer else "input"
                ctx.error(
                    "float-leak", path,
                    f"float->int conversion fed by '{pname}' with no "
                    "explicit floor/round barrier - truncation of "
                    "context-dependent float bits leaks into the "
                    "integer coder",
                    "apply jnp.floor/jnp.round before .astype so the "
                    "integer boundary is explicit and canonical")
        for sub in _sub_jaxprs(eqn.params):
            _scan_jaxpr(sub, ctx, path, seen_rules)


def _jaxpr_rules(leaf: Codec, stack: ans.ANSStack, value, ctx: _Ctx,
                 path: str) -> None:
    seen: set = set()
    try:
        closed = jax.make_jaxpr(lambda st: leaf.pop(st))(stack)
    except Exception as e:   # pragma: no cover - trace-hostile leaf
        ctx.note("opaque-probe", path,
                 f"pop is not traceable ({type(e).__name__}); jaxpr "
                 "rules skipped")
        return
    _scan_jaxpr(closed.jaxpr, ctx, path + ".pop", seen)
    if value is None:
        return
    try:
        closed = jax.make_jaxpr(lambda st, v: leaf.push(st, v))(stack, value)
    except Exception:        # pragma: no cover
        return
    _scan_jaxpr(closed.jaxpr, ctx, path + ".push", seen)


# ---------------------------------------------------------------------------
# frequency-table soundness
# ---------------------------------------------------------------------------

def _check_starts(F: np.ndarray, precision: int, ctx: _Ctx, path: str,
                  idx: Optional[np.ndarray] = None) -> float:
    """Check a cumulative-starts array F[..., A+1] (int64); returns the
    worst-case bits one symbol under this table can cost."""
    total = 1 << precision
    gaps = np.diff(idx) if idx is not None \
        else np.ones(F.shape[-1] - 1, np.int64)
    first, last = F[..., 0], F[..., -1]
    if (first != 0).any() or (last != total).any():
        ctx.error(
            "freq-sum", path,
            f"table spans [{int(first.min())}, {int(last.max())}] "
            f"instead of exactly [0, 2^{precision}] - slots outside the "
            "span decode to garbage or crash",
            "build tables with ans.cdf_to_starts/probs_to_starts, which "
            "are exact-total by construction")
    d = np.diff(F, axis=-1)
    if (d < 0).any():
        ctx.error(
            "starts-monotone", path,
            "cumulative starts decrease - the decode search is "
            "ill-defined",
            "the underlying CDF must be non-decreasing; clip or sort "
            "the float CDF before quantizing")
    elif (d < gaps).any():
        ctx.error(
            "freq-zero", path,
            "a symbol has zero frequency - pushing it corrupts the "
            "stack and its slot silently decodes to a neighbour",
            "reserve at least 1/2^precision mass per symbol (the +i "
            "ramp of ans.cdf_to_starts does this)")
    min_freq = max(int(d.min()) if d.size else 1, 1)
    return precision - float(np.floor(np.log2(min_freq)))


def _grid_starts(f: Callable, k: int, lanes: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Evaluate a pointwise starts fn on the bucket grid; returns
    (F[n_pts, lanes] int64, idx[n_pts]). Samples when K is huge."""
    if k <= 4096:
        idx = np.arange(k + 1, dtype=np.int32)
    else:
        stride = k // 2048
        idx = np.unique(np.concatenate(
            [np.arange(0, k + 1, stride, dtype=np.int32),
             np.asarray([0, 1, k - 1, k], np.int32)]))
    grid = jnp.asarray(idx)[:, None] * jnp.ones((1, lanes), jnp.int32)
    try:
        F = jax.vmap(f)(grid)
    except Exception:
        F = jnp.stack([f(jnp.full((lanes,), int(i), jnp.int32))
                       for i in idx])
    return np.asarray(F).astype(np.int64).T, idx.astype(np.int64)


def _check_grid(f: Callable, bits: int, precision: int, lanes: int,
                ctx: _Ctx, path: str) -> float:
    try:
        F, idx = _grid_starts(f, 1 << bits, lanes)
    except Exception as e:
        ctx.error("freq-sum", path,
                  f"starts function failed on the bucket grid: "
                  f"{type(e).__name__}: {e}",
                  "the pointwise CDF must accept any index in [0, 2^bits]")
        return float(precision)
    return _check_starts(F, precision, ctx, path, idx)


# ---------------------------------------------------------------------------
# leaf checks
# ---------------------------------------------------------------------------

def _leaf_mirror(leaf: Codec, stack: ans.ANSStack, ctx: _Ctx, path: str
                 ) -> Tuple[ans.ANSStack, Any]:
    """Pop one symbol, push it back, and require (a) the reversed push
    events to equal the pop events and (b) the stack to return
    bit-identically. Returns the post-pop state so the walk advances."""
    rec = _Recorder()
    try:
        with _recording(rec):
            popped, value = leaf.pop(stack)
            restored = leaf.push(popped, value)
    except Exception as e:
        ctx.error("push-pop-mirror", path,
                  f"pop/push probe raised {type(e).__name__}: {e}",
                  "a leaf must decode from any stack state")
        return stack, None
    pops = [e for e in rec.events if e[0] == "pop"]
    pushes = [e for e in rec.events if e[0] == "push"]
    mismatch = None
    if len(pops) != len(pushes):
        mismatch = (f"{len(pops)} pop event(s) vs {len(pushes)} push "
                    "event(s)")
    else:
        for i, (po, pu) in enumerate(zip(pops, reversed(pushes))):
            if po[3] != pu[3]:
                mismatch = f"precision differs at event {i}"
                break
            if not (np.array_equal(po[1], pu[1])
                    and np.array_equal(po[2], pu[2])):
                mismatch = f"(start, freq) differ at event {i}"
                break
    if mismatch is None:
        mismatch = _stacks_equal(stack, restored)
        if mismatch is not None:
            mismatch = f"stack not restored ({mismatch})"
    if mismatch is not None:
        ctx.error(
            "push-pop-mirror", path,
            f"push is not the mirror inverse of pop: {mismatch}",
            "push(stack, x) and pop must hand ans the identical "
            "(start, freq, precision) for the same symbol")
    return popped, value


def _check_leaf(leaf: Codec, stack: ans.ANSStack, ctx: _Ctx, path: str
                ) -> Tuple[ans.ANSStack, Any, float]:
    """Full leaf battery; returns (advanced stack, value, bits bound)."""
    lanes = stack.lanes
    precision = getattr(leaf, "precision", ans.DEFAULT_PRECISION)
    bound = float(precision)

    if isinstance(leaf, L.Uniform):
        if not 0 < leaf.bits <= precision:
            ctx.error("freq-sum", path,
                      f"Uniform(bits={leaf.bits}) does not fit precision "
                      f"{precision}",
                      "need 0 < bits <= precision")
        bound = float(leaf.bits)
    elif isinstance(leaf, L.DiscretizedGaussian):
        f = discretize.posterior_starts_fn(leaf.mu, leaf.sigma, leaf.bits,
                                           precision)
        bound = _check_grid(f, leaf.bits, precision, lanes, ctx, path)
    elif isinstance(leaf, L.DiscretizedLogistic):
        f = L.logistic_starts_fn(leaf.mu, leaf.scale, leaf.bits, precision)
        bound = _check_grid(f, leaf.bits, precision, lanes, ctx, path)
    elif isinstance(leaf, L.PointwiseCDF):
        try:
            f = leaf._starts()
        except Exception as e:
            ctx.error("freq-sum", path, f"_starts() raised: {e}")
            f = None
        if f is not None:
            bound = _check_grid(f, leaf.bits, precision, lanes, ctx, path)
    elif isinstance(leaf, (Bernoulli, BetaBinomial, Categorical)):
        try:
            if isinstance(leaf, Bernoulli):
                f1 = np.asarray(leaf._freq1()).astype(np.int64)
                total = 1 << precision
                F = np.stack([np.zeros_like(f1), total - f1,
                              np.full_like(f1, total)], axis=-1)
            else:
                F = np.asarray(leaf._table()).astype(np.int64)
        except Exception as e:
            ctx.error("freq-sum", path, f"table build raised: {e}")
            F = None
        if F is not None:
            bound = _check_starts(F, precision, ctx, path)
    elif isinstance(leaf, FactoredCategorical):
        grouped, chunk_logits, n_chunks = leaf._parts()
        inner = Categorical(grouped[:, 0], precision)
        bound = _check_starts(np.asarray(inner._table()).astype(np.int64),
                              precision, ctx, path + "[chunk 0]")
        if n_chunks > 1:
            outer = Categorical(chunk_logits, precision)
            bound += _check_starts(
                np.asarray(outer._table()).astype(np.int64),
                precision, ctx, path + "[chunk marginal]")

    stack, value = _leaf_mirror(leaf, stack, ctx, path)
    _jaxpr_rules(leaf, stack, value, ctx, path)
    return stack, value, bound


# ---------------------------------------------------------------------------
# the collection walk
# ---------------------------------------------------------------------------

_LEAF_TYPES = (L.Uniform, L.DiscretizedGaussian, L.DiscretizedLogistic,
               L.PointwiseCDF, Bernoulli, BetaBinomial, Categorical,
               FactoredCategorical)


def _stream_types():
    from repro.stream import coder as stream_coder
    return stream_coder.BlockChain, stream_coder.KernelTableBlock


def _carries_model_floats(codec: Codec) -> bool:
    """True when coding this subtree evaluates float arithmetic whose
    bits could depend on the surrounding compilation context."""
    codec = _unwrap(codec)
    if isinstance(codec, L.Uniform):
        return False
    if isinstance(codec, C.Serial):
        return any(_carries_model_floats(c) for c in codec.codecs)
    if isinstance(codec, C.Shaped):
        return _carries_model_floats(codec.inner)
    if isinstance(codec, C.TreeCodec):
        leaves, _ = jax.tree_util.tree_flatten(
            codec.tree, is_leaf=lambda c: isinstance(c, Codec))
        return any(_carries_model_floats(c) for c in leaves)
    if isinstance(codec, C.Repeat):
        try:
            return _carries_model_floats(codec.codec_fn(0))
        except Exception:
            return True
    if isinstance(codec, C.Chained):
        return _carries_model_floats(codec.inner)
    return True


def _build_child(fn: Callable, arg, ctx: _Ctx, path: str) -> Optional[Codec]:
    try:
        child = fn(arg)
    except Exception as e:
        ctx.error("child-build", path,
                  f"building the child codec raised {type(e).__name__}: "
                  f"{e}",
                  "likelihood/posterior functions must accept any value "
                  "their argument codec can decode")
        return None
    if not isinstance(child, Codec):
        ctx.error("child-build", path,
                  f"child builder returned {type(child).__name__}, not a "
                  "Codec")
        return None
    return child


def _walk(codec: Codec, path: str, stack: ans.ANSStack, ctx: _Ctx,
          depth: int = 0) -> Tuple[ans.ANSStack, Any]:
    """Decode-ordered traversal; returns (advanced stack, decoded value).

    ``ctx.bound`` accumulates the worst-case bits a *push* of one
    datapoint can add (posterior pops give bits back, so fork-walked
    posteriors are excluded)."""
    if depth > 64:
        ctx.warn("opaque-probe", path, "tree deeper than 64 levels; "
                 "stopping the walk here")
        return stack, None
    codec = _unwrap(codec)

    if isinstance(codec, _LEAF_TYPES):
        stack, value, bound = _check_leaf(codec, stack, ctx, path)
        ctx.bound += bound
        return stack, value

    if isinstance(codec, C.Serial):
        out = []
        for i, child in enumerate(codec.codecs):
            stack, v = _walk(child, f"{path}.codecs[{i}]", stack, ctx,
                             depth + 1)
            out.append(v)
        return stack, tuple(out)

    if isinstance(codec, C.Shaped):
        stack, flat = _walk(codec.inner, path + ".inner", stack, ctx,
                            depth + 1)
        if flat is not None:
            flat = flat.reshape((flat.shape[0],) + tuple(codec.shape))
        return stack, flat

    if isinstance(codec, C.TreeCodec):
        leaves, treedef = jax.tree_util.tree_flatten(
            codec.tree, is_leaf=lambda c: isinstance(c, Codec))
        out = []
        for i, child in enumerate(leaves):
            stack, v = _walk(child, f"{path}.tree[{i}]", stack, ctx,
                             depth + 1)
            out.append(v)
        return stack, treedef.unflatten(out)

    if isinstance(codec, C.Repeat):
        n = codec.n
        probe_bounds = [0.0]
        for d in sorted({0, n // 2, n - 1} & set(range(max(n, 0)))):
            try:
                leaf = codec.codec_fn(d)
            except Exception as e:
                ctx.error("child-build", f"{path}.codec_fn({d})",
                          f"codec_fn raised {type(e).__name__}: {e}")
                continue
            save = ctx.bound
            _walk(leaf, f"{path}.codec_fn({d})", stack, ctx, depth + 1)
            probe_bounds.append(ctx.bound - save)
            ctx.bound = save
        ctx.bound += n * max(probe_bounds)
        try:
            return codec.pop(stack)
        except Exception as e:
            ctx.error("opaque-probe", path,
                      f"Repeat.pop raised {type(e).__name__}: {e}")
            return stack, None

    if isinstance(codec, C.Chained):
        if codec.scan and _carries_model_floats(codec.inner):
            ctx.error(
                "scan-chain", path,
                "Chained(scan=True) over a codec that evaluates model "
                "floats - lax.scan fuses the chain body into one "
                "program per direction, where XLA may produce float32 "
                "bits that differ from the eager path by an ulp",
                "use the default scan=False (the Python chain loop), "
                "or codecs.compile for a fast fused chain")
        save = ctx.bound
        stack, v = _walk(codec.inner, path + ".inner", stack, ctx,
                         depth + 1)
        ctx.bound = save + codec.n * (ctx.bound - save)
        value = None if v is None else jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * codec.n, axis=0), v)
        return stack, value

    if isinstance(codec, C.BBANS):
        stack, y = _walk(codec.prior, path + ".prior", stack, ctx,
                         depth + 1)
        lik = _build_child(codec.likelihood, y, ctx,
                           path + ".likelihood(y)")
        if lik is None:
            return stack, None
        stack, s = _walk(lik, path + ".likelihood(y)", stack, ctx,
                         depth + 1)
        post = _build_child(codec.posterior, s, ctx, path + ".posterior(s)")
        if post is None:
            return stack, s
        save = ctx.bound   # posterior pops give bits back: fork-check only
        _walk(post, path + ".posterior(s)", stack, ctx, depth + 1)
        ctx.bound = save
        try:
            stack = post.push(stack, y)
        except Exception as e:
            ctx.error("push-pop-mirror", path + ".posterior(s)",
                      f"posterior push raised {type(e).__name__}: {e}",
                      "the posterior must encode any value the prior "
                      "decodes")
        return stack, s

    if isinstance(codec, C.BitSwap):
        stack, z = _walk(codec.prior, path + ".prior", stack, ctx,
                         depth + 1)
        for i in range(len(codec.layers) - 1, -1, -1):
            posterior_fn, likelihood_fn = codec.layers[i]
            lik = _build_child(likelihood_fn, z, ctx,
                               f"{path}.layers[{i}].likelihood(z)")
            if lik is None:
                return stack, None
            stack, ctx_val = _walk(lik, f"{path}.layers[{i}].likelihood(z)",
                                   stack, ctx, depth + 1)
            post = _build_child(posterior_fn, ctx_val, ctx,
                                f"{path}.layers[{i}].posterior(ctx)")
            if post is None:
                return stack, ctx_val
            save = ctx.bound
            _walk(post, f"{path}.layers[{i}].posterior(ctx)", stack, ctx,
                  depth + 1)
            ctx.bound = save
            try:
                stack = post.push(stack, z)
            except Exception as e:
                ctx.error("push-pop-mirror",
                          f"{path}.layers[{i}].posterior(ctx)",
                          f"posterior push raised {type(e).__name__}: {e}")
            z = ctx_val
        return stack, z

    BlockChain, KernelTableBlock = _stream_types()
    if isinstance(codec, BlockChain):
        save = ctx.bound
        stack, v = _walk(codec.inner, path + ".inner", stack, ctx,
                         depth + 1)
        ctx.bound = save + codec.k * (ctx.bound - save)
        value = None if v is None else jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * codec.k, axis=0), v)
        return stack, value
    if isinstance(codec, KernelTableBlock):
        per = _check_starts(np.asarray(codec.table).astype(np.int64),
                            codec.precision, ctx, path)
        ctx.bound += codec.k * per
        try:
            return codec.pop(stack)
        except Exception as e:
            ctx.error("opaque-probe", path,
                      f"pop raised {type(e).__name__}: {e}")
            return stack, None

    # Opaque: FnCodec, TokenStream, anything marked or unknown.
    if not getattr(codec, "__analysis_opaque__", False):
        ctx.note(
            "opaque-probe", path,
            f"unknown codec class {type(codec).__name__}: probed for "
            "inversion only (tables and jaxprs not inspected)",
            "mark the class __analysis_opaque__ = True if this is "
            "intentional (a driver codec managing its own jit programs)")
    ctx.bound_exact = False
    try:
        return codec.pop(stack)
    except Exception as e:
        ctx.error("opaque-probe", path,
                  f"pop raised {type(e).__name__}: {e}",
                  "every codec must decode from any stack state")
        return stack, None


# ---------------------------------------------------------------------------
# the two passes + entry points
# ---------------------------------------------------------------------------

def _check_edge_cache(ctx: _Ctx) -> None:
    for name, fn in (("edge_table", discretize.edge_table),
                     ("centre_table", discretize.centre_table)):
        a, b = fn(8), fn(8)
        if a is not b:
            ctx.error(
                "edge-cache", f"core.discretize.{name}",
                "bucket-geometry table is rebuilt per call instead of "
                "cached - ndtri recomputation can hand different bits "
                "to encode and decode",
                "memoize the table per lat_bits and build it inside "
                "jax.ensure_compile_time_eval()")
        elif isinstance(a, jax.core.Tracer):
            ctx.error("edge-cache", f"core.discretize.{name}",
                      "bucket-geometry table is a tracer, not a "
                      "concrete array")


def _inverse_probe(codec: Codec, ctx: _Ctx, lanes: int, seed: int,
                   init_chunks: int, retries: int = 4) -> None:
    chunks, cap = init_chunks, init_chunks + 512
    for _ in range(retries):
        s0 = fresh_stack(lanes, cap, seed, chunks)
        try:
            s1, x = codec.pop(s0)
            s2 = codec.push(s1, x)
        except Exception as e:
            ctx.error(
                "inverse-probe", "codec",
                f"pop/push probe raised {type(e).__name__}: {e}",
                "the tree must decode from a fresh seeded stack and "
                "re-encode what it decoded")
            return
        if int(jnp.sum(s2.underflows)):
            chunks *= 4
            cap = chunks + 512
            continue
        if int(jnp.sum(s2.overflows)):
            cap *= 2
            continue
        diff = _stacks_equal(s0, s2)
        if diff is not None:
            ctx.error(
                "inverse-probe", "codec",
                f"push(pop(stack)) is not bit-identical: {diff}",
                "some leaf or driver in this tree encodes with different "
                "(start, freq) than it decodes - the per-leaf "
                "push-pop-mirror finding (if any) names it")
        return
    ctx.error(
        "inverse-probe", "codec",
        "probe never completed cleanly (persistent stack under/overflow "
        f"after {retries} growth retries)",
        "pushes and pops are likely unbalanced somewhere in this tree")


def verify_codec(codec: Codec, *, lanes: int = 4, seed: int = 0,
                 init_chunks: int = 256, capacity: Optional[int] = None,
                 max_retries: int = 4,
                 context: str = "verify_codec") -> Report:
    """Statically verify a ``Codec`` tree; returns a ``Report``.

    No user data is coded: both passes run against a scratch stack
    seeded deterministically from ``seed``. ``lanes`` is the probe
    width (codecs are lane-polymorphic, so small is fine - but a codec
    built for a fixed lane count must be probed at that count).
    ``capacity`` (in 16-bit chunks per lane), when given, is checked
    against the tree's worst-case bits-per-datapoint bound (rule
    ``capacity-bound``).

    Example::

        report = verify_codec(make_bb_codec(params, cfg), lanes=2)
        assert report.ok, str(report)
    """
    codec = _unwrap(codec)
    ctx = _Ctx()
    _check_edge_cache(ctx)
    _inverse_probe(codec, ctx, lanes, seed, init_chunks)

    chunks = init_chunks
    cap = chunks + 512
    for _ in range(max_retries):
        trial = _Ctx()
        stack = fresh_stack(lanes, cap, seed, chunks)
        stack, _ = _walk(codec, "codec", stack, trial)
        if int(jnp.sum(stack.underflows)):
            chunks *= 4
            cap = chunks + 512
            continue
        if int(jnp.sum(stack.overflows)):
            cap *= 2
            continue
        break
    ctx.findings.extend(trial.findings)
    ctx.notes.extend(trial.notes)
    bound = trial.bound if trial.bound_exact else None

    if capacity is not None and trial.bound > capacity * 16:
        need = int(np.ceil(trial.bound / 16))
        more = "at least " if not trial.bound_exact else ""
        ctx.warn(
            "capacity-bound", "codec",
            f"worst case pushes {more}{trial.bound:.0f} bits/lane per "
            f"datapoint but capacity {capacity} holds {capacity * 16} - "
            "the first encode attempt can overflow and burn a "
            "grow-and-retry cycle",
            f"start with capacity >= {need} chunks/lane per datapoint")

    return Report(context=context, findings=tuple(ctx.findings),
                  notes=tuple(ctx.notes), bits_bound=bound)


def check_codec(codec: Codec, **kwargs) -> Report:
    """``verify_codec`` that raises ``ContractViolation`` on errors
    (warnings and notes do not raise). Returns the clean ``Report``.

    Example::

        report = check_codec(codecs.Uniform(8), lanes=2)
    """
    report = verify_codec(codec, **kwargs)
    if not report.ok:
        raise ContractViolation(report)
    return report


def bits_bound(codec: Codec, *, lanes: int = 4, seed: int = 0
               ) -> Optional[float]:
    """Worst-case bits one datapoint can push per lane, or ``None``
    when the tree contains opaque leaves (their cost is not statically
    knowable).

    Example::

        assert bits_bound(codecs.Uniform(8), lanes=2) == 8.0
    """
    return verify_codec(codec, lanes=lanes, seed=seed).bits_bound

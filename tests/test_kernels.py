"""Pallas kernels vs pure-jnp oracles: shape/dtype/precision sweeps in
interpret mode (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ans, discretize
from repro.kernels.ans import ops as ans_ops, ref as ans_ref
from repro.kernels.bucketize import ops as bk_ops, ref as bk_ref
from repro.kernels.flash import ops as fl_ops, ref as fl_ref


# ---------------------------------------------------------------------------
# ANS push kernel
# ---------------------------------------------------------------------------

def _rand_symbol_stream(rng, steps, lanes, alphabet, precision):
    starts = np.zeros((steps, lanes), np.uint32)
    freqs = np.zeros((steps, lanes), np.uint32)
    for t in range(steps):
        probs = rng.dirichlet(np.ones(alphabet), size=lanes)
        table = np.asarray(ans.probs_to_starts(
            jnp.asarray(probs, jnp.float32), precision))
        sym = rng.integers(0, alphabet, lanes)
        starts[t] = table[np.arange(lanes), sym]
        freqs[t] = table[np.arange(lanes), sym + 1] - starts[t]
    return jnp.asarray(starts), jnp.asarray(freqs)


@pytest.mark.parametrize("steps,lanes,alphabet,precision", [
    (4, 8, 4, 12),
    (16, 64, 17, 16),
    (9, 130, 3, 8),     # lanes not a multiple of the tile
    (32, 128, 256, 16),
])
def test_ans_push_kernel_matches_core(steps, lanes, alphabet, precision):
    rng = np.random.default_rng(steps * 1000 + lanes)
    starts, freqs = _rand_symbol_stream(rng, steps, lanes, alphabet,
                                        precision)
    stack = ans.make_stack(lanes, capacity=steps + 8,
                           key=jax.random.PRNGKey(lanes))
    out_kernel = ans_ops.push_many(stack, starts, freqs, precision)
    out_ref = ans_ref.push_many_ref(stack, starts, freqs, precision)
    np.testing.assert_array_equal(np.asarray(out_kernel.head),
                                  np.asarray(out_ref.head))
    np.testing.assert_array_equal(np.asarray(out_kernel.ptr),
                                  np.asarray(out_ref.ptr))
    np.testing.assert_array_equal(np.asarray(out_kernel.buf),
                                  np.asarray(out_ref.buf))


@pytest.mark.parametrize("steps,lanes,alphabet,precision", [
    (4, 8, 4, 12),
    (16, 64, 17, 16),
    (9, 130, 3, 8),     # lanes not a multiple of the tile
    (32, 128, 200, 16),
])
def test_ans_pop_kernel_matches_core(steps, lanes, alphabet, precision):
    """Table-driven pop_many == sequential ans.pop_with_table, bit for
    bit (head, ptr, symbols, underflow counters)."""
    rng = np.random.default_rng(steps * 977 + lanes)
    probs = rng.dirichlet(np.ones(alphabet), size=lanes)
    table = ans.probs_to_starts(jnp.asarray(probs, jnp.float32), precision)
    syms = jnp.asarray(rng.integers(0, alphabet, (steps, lanes)),
                       jnp.int32)
    stack = ans.make_stack(lanes, steps + 8, key=jax.random.PRNGKey(7))
    stack = ans_ops.push_many_table(stack, table, syms, precision)
    ref_stack = ans_ref.push_many_table_ref(stack, table, syms, precision)

    out_k, syms_k = ans_ops.pop_many(stack, table, steps, precision)
    out_r, syms_r = ans_ref.pop_many_ref(stack, table, steps, precision)
    np.testing.assert_array_equal(np.asarray(syms_k), np.asarray(syms_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.ptr),
                                  np.asarray(out_r.ptr))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))
    # and the pushed symbols come back reversed (LIFO)
    np.testing.assert_array_equal(np.asarray(syms_k),
                                  np.asarray(syms)[::-1])


def test_ans_pop_kernel_underflow_matches_core():
    """Pops past the stack bottom must count underflows and mangle the
    head exactly as the core does (bottom chunk re-served)."""
    rng = np.random.default_rng(3)
    lanes, precision = 6, 10
    probs = rng.dirichlet(np.ones(4), size=lanes)
    table = ans.probs_to_starts(jnp.asarray(probs, jnp.float32), precision)
    stack = ans.make_stack(lanes, 4)   # cold head, empty buffer
    out_k, syms_k = ans_ops.pop_many(stack, table, 12, precision)
    out_r, syms_r = ans_ref.pop_many_ref(stack, table, 12, precision)
    np.testing.assert_array_equal(np.asarray(syms_k), np.asarray(syms_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))
    assert int(jnp.sum(out_k.underflows)) > 0


def test_peek_kernel_matches_core_peek():
    """pop_slots is the honest single-step peek: slot = head mod 2^p."""
    rng = np.random.default_rng(5)
    lanes = 256
    head = jnp.asarray(
        rng.integers(1 << 16, 1 << 32, lanes, dtype=np.uint64)
        .astype(np.uint32))
    from repro.kernels.ans import kernel as ans_kernel
    for precision in (8, 12, 16):
        slots = ans_kernel.pop_slots(head, precision)
        expect = ans.peek(
            ans.make_stack(lanes, 1)._replace(head=head), precision)
        np.testing.assert_array_equal(np.asarray(slots),
                                      np.asarray(expect))


def test_ans_push_kernel_then_core_pop_roundtrip():
    """Kernel-encoded stream decodes with the core library."""
    rng = np.random.default_rng(7)
    lanes, steps, alphabet, precision = 8, 12, 5, 12
    probs = rng.dirichlet(np.ones(alphabet), size=lanes)
    table = ans.probs_to_starts(jnp.asarray(probs, jnp.float32), precision)
    syms = rng.integers(0, alphabet, (steps, lanes))
    tab_np = np.asarray(table)
    starts = jnp.asarray(tab_np[np.arange(lanes)[None], syms], jnp.uint32)
    freqs = jnp.asarray(
        tab_np[np.arange(lanes)[None], syms + 1] -
        tab_np[np.arange(lanes)[None], syms], jnp.uint32)

    stack = ans.make_stack(lanes, 32, key=jax.random.PRNGKey(3))
    stack = ans_ops.push_many(stack, starts, freqs, precision)
    for t in reversed(range(steps)):
        stack, out = ans.pop_with_table(stack, table, precision)
        np.testing.assert_array_equal(np.asarray(out), syms[t])


# ---------------------------------------------------------------------------
# Dynamic-table pop kernel (per-step tables)
# ---------------------------------------------------------------------------

def _dyn_tables(rng, steps, lanes, alphabet, precision):
    tabs = []
    for _ in range(steps):
        probs = rng.dirichlet(np.ones(alphabet), size=lanes)
        tabs.append(np.asarray(ans.probs_to_starts(
            jnp.asarray(probs, jnp.float32), precision)))
    return jnp.asarray(np.stack(tabs), jnp.uint32)


@pytest.mark.parametrize("steps,lanes,alphabet,precision", [
    (4, 8, 4, 12),
    (16, 64, 17, 16),
    (9, 130, 3, 8),     # lanes not a multiple of the tile
    (12, 128, 100, 16),
])
def test_ans_pop_dyn_kernel_matches_ref(steps, lanes, alphabet, precision):
    """pop_many_dyn == sequential ans.pop_with_table against the
    per-step tables, bit for bit."""
    rng = np.random.default_rng(steps * 31 + lanes)
    tables = _dyn_tables(rng, steps, lanes, alphabet, precision)
    stack = ans.make_stack(lanes, steps + 8, key=jax.random.PRNGKey(11))
    stack = ans.seed_stack(stack, jax.random.PRNGKey(12), steps)
    out_k, syms_k = ans_ops.pop_many_dyn(stack, tables, precision)
    out_r, syms_r = ans_ref.pop_many_dyn_ref(stack, tables, precision)
    np.testing.assert_array_equal(np.asarray(syms_k), np.asarray(syms_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.ptr),
                                  np.asarray(out_r.ptr))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))


def test_ans_pop_dyn_roundtrips_dynamic_push():
    """Dynamic push (push_many) then dynamic pop (pop_many_dyn) against
    the same per-step tables recovers the symbols reversed (LIFO)."""
    rng = np.random.default_rng(21)
    steps, lanes, alphabet, precision = 10, 6, 7, 14
    tables = _dyn_tables(rng, steps, lanes, alphabet, precision)
    syms = jnp.asarray(rng.integers(0, alphabet, (steps, lanes)),
                       jnp.int32)
    tab_np = np.asarray(tables)
    rows = np.arange(lanes)[None, :]
    starts = jnp.asarray(
        tab_np[np.arange(steps)[:, None], rows, np.asarray(syms)],
        jnp.uint32)
    freqs = jnp.asarray(
        tab_np[np.arange(steps)[:, None], rows, np.asarray(syms) + 1],
        jnp.uint32) - starts
    stack = ans.make_stack(lanes, steps + 8, key=jax.random.PRNGKey(13))
    stack = ans_ops.push_many(stack, starts, freqs, precision)
    # pop order reverses push order, so tables are consumed flipped
    out, decoded = ans_ops.pop_many_dyn(stack, tables[::-1], precision)
    np.testing.assert_array_equal(np.asarray(decoded),
                                  np.asarray(syms)[::-1])


def test_ans_pop_dyn_underflow_matches_ref():
    """Underflow edge: pops past the stack bottom count and mangle the
    head exactly as the sequential core does."""
    rng = np.random.default_rng(22)
    steps, lanes, precision = 12, 6, 10
    tables = _dyn_tables(rng, steps, lanes, 4, precision)
    stack = ans.make_stack(lanes, 4)   # cold head, empty buffer
    out_k, syms_k = ans_ops.pop_many_dyn(stack, tables, precision)
    out_r, syms_r = ans_ref.pop_many_dyn_ref(stack, tables, precision)
    np.testing.assert_array_equal(np.asarray(syms_k), np.asarray(syms_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))
    assert int(jnp.sum(out_k.underflows)) > 0


def test_ans_push_kernel_overflow_edge_matches_ref():
    """Overflow edge: chunks dropped past capacity are counted
    identically by the kernel path and the sequential core."""
    rng = np.random.default_rng(23)
    steps, lanes, precision = 24, 6, 12
    starts, freqs = _rand_symbol_stream(rng, steps, lanes, 4, precision)
    stack = ans.make_stack(lanes, 4, key=jax.random.PRNGKey(24))  # tiny
    out_k = ans_ops.push_many(stack, starts, freqs, precision)
    out_r = ans_ref.push_many_ref(stack, starts, freqs, precision)
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.overflows),
                                  np.asarray(out_r.overflows))
    assert int(jnp.sum(out_k.overflows)) > 0


# ---------------------------------------------------------------------------
# Fused bucketize+pop grid kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["gaussian", "logistic", "uniform"])
@pytest.mark.parametrize("steps,lanes,lat_bits,precision", [
    (5, 8, 8, 16),
    (16, 64, 10, 16),
    (7, 130, 6, 12),    # lanes not a multiple of the tile
])
def test_ans_pop_grid_kernel_matches_ref(kind, steps, lanes, lat_bits,
                                         precision):
    """pop_many_grid == sequential per-position leaf pops (the fused
    CDF-inversion-in-renorm-chain kernel vs the core library)."""
    rng = np.random.default_rng(steps * 13 + lanes + lat_bits)
    mu = jnp.asarray(rng.normal(0, 1.2, (steps, lanes)), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.05, 2.0, (steps, lanes)),
                        jnp.float32)
    stack = ans.make_stack(lanes, steps + 8, key=jax.random.PRNGKey(31))
    stack = ans.seed_stack(stack, jax.random.PRNGKey(32), steps)
    out_k, idx_k = ans_ops.pop_many_grid(stack, kind, mu, sigma, steps,
                                         lat_bits, precision)
    out_r, idx_r = ans_ref.pop_many_grid_ref(stack, kind, mu, sigma,
                                             steps, lat_bits, precision)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.ptr),
                                  np.asarray(out_r.ptr))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))


def test_ans_pop_grid_underflow_matches_ref():
    rng = np.random.default_rng(33)
    steps, lanes, lat_bits, precision = 10, 6, 8, 16
    mu = jnp.asarray(rng.normal(0, 1, (steps, lanes)), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.1, 1.5, (steps, lanes)),
                        jnp.float32)
    stack = ans.make_stack(lanes, 4)   # cold head, empty buffer
    out_k, idx_k = ans_ops.pop_many_grid(stack, "gaussian", mu, sigma,
                                         steps, lat_bits, precision)
    out_r, idx_r = ans_ref.pop_many_grid_ref(stack, "gaussian", mu,
                                             sigma, steps, lat_bits,
                                             precision)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(out_k.head),
                                  np.asarray(out_r.head))
    np.testing.assert_array_equal(np.asarray(out_k.underflows),
                                  np.asarray(out_r.underflows))
    assert int(jnp.sum(out_k.underflows)) > 0


@pytest.mark.parametrize("op", ["push", "pop_table", "pop_dyn",
                                "grid_gaussian", "grid_uniform"])
def test_step_blocked_kernels_match_ref(op, monkeypatch):
    """A VMEM budget of a few steps splits every multi-step kernel into
    8-step blocks plus a short tail; the carried heads and read
    counters must cross the block boundaries bit-exactly."""
    from repro.kernels.ans import kernel as ans_kernel
    monkeypatch.setattr(ans_kernel, "_STEP_BLOCK_BYTES", 1)
    rng = np.random.default_rng(41)
    steps, lanes, precision = 21, 8, 12
    stack = ans.seed_stack(
        ans.make_stack(lanes, 4 * steps, key=jax.random.PRNGKey(42)),
        jax.random.PRNGKey(43), steps)
    if op == "push":
        starts, freqs = _rand_symbol_stream(rng, steps, lanes, 5,
                                            precision)
        out_k = ans_ops.push_many(stack, starts, freqs, precision,
                                  backend="interpret")
        out_r = ans_ref.push_many_ref(stack, starts, freqs, precision)
        for field in ("head", "buf", "ptr", "overflows"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out_k, field)),
                np.asarray(getattr(out_r, field)))
        return
    if op == "pop_table":
        table = ans.probs_to_starts(jnp.asarray(
            rng.dirichlet(np.ones(6), size=lanes), jnp.float32), precision)
        out_k, syms_k = ans_ops.pop_many(stack, table, steps, precision,
                                         backend="interpret")
        out_r, syms_r = ans_ref.pop_many_ref(stack, table, steps,
                                             precision)
    elif op == "pop_dyn":
        tables = _dyn_tables(rng, steps, lanes, 6, precision)
        out_k, syms_k = ans_ops.pop_many_dyn(stack, tables, precision,
                                             backend="interpret")
        out_r, syms_r = ans_ref.pop_many_dyn_ref(stack, tables, precision)
    else:
        kind = op.split("_")[1]
        mu = jnp.asarray(rng.normal(0, 1, (steps, lanes)), jnp.float32)
        sigma = jnp.asarray(rng.uniform(0.1, 1.5, (steps, lanes)),
                            jnp.float32)
        out_k, syms_k = ans_ops.pop_many_grid(stack, kind, mu, sigma, steps,
                                              6, precision,
                                              backend="interpret")
        out_r, syms_r = ans_ref.pop_many_grid_ref(stack, kind, mu, sigma,
                                                  steps, 6, precision)
    np.testing.assert_array_equal(np.asarray(syms_k), np.asarray(syms_r))
    for field in ("head", "ptr", "underflows"):
        np.testing.assert_array_equal(np.asarray(getattr(out_k, field)),
                                      np.asarray(getattr(out_r, field)))


# ---------------------------------------------------------------------------
# Bucketize kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes,lat_bits,precision", [
    (8, 8, 16), (64, 10, 16), (200, 12, 16), (128, 6, 12),
])
def test_bucketize_kernel_matches_ref(lanes, lat_bits, precision):
    rng = np.random.default_rng(lanes)
    slot = jnp.asarray(rng.integers(0, 1 << precision, lanes), jnp.uint32)
    mu = jnp.asarray(rng.normal(0, 1.2, lanes), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.05, 2.0, lanes), jnp.float32)
    idx_k, st_k, fr_k = bk_ops.bucketize(slot, mu, sigma, lat_bits,
                                         precision)
    idx_r, st_r, fr_r = bk_ref.bucketize_ref(slot, mu, sigma, lat_bits,
                                             precision)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(st_k), np.asarray(st_r))
    np.testing.assert_array_equal(np.asarray(fr_k), np.asarray(fr_r))


def test_bucketize_kernel_matches_discretize_pop():
    """Kernel output == what core.discretize.pop_posterior decodes."""
    lanes, lat_bits, prec = 16, 10, 16
    rng = np.random.default_rng(5)
    mu = jnp.asarray(rng.normal(0, 1, lanes), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.1, 1.5, lanes), jnp.float32)
    stack = ans.make_stack(lanes, 16, key=jax.random.PRNGKey(9))
    slot = ans.peek(stack, prec)
    idx_k, _, _ = bk_ops.bucketize(slot, mu, sigma, lat_bits, prec)
    _, idx_core = discretize.pop_posterior(stack, mu, sigma, lat_bits,
                                           prec)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_core))


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,d,causal,window,dtype", [
    (64, 64, 16, True, 0, jnp.float32),
    (128, 128, 32, True, 40, jnp.float32),
    (96, 160, 16, False, 0, jnp.float32),
    (100, 84, 8, True, 0, jnp.float32),     # non-multiples of block
    (64, 64, 16, True, 0, jnp.bfloat16),
])
def test_flash_kernel_matches_sdpa(sq, sk, d, causal, window, dtype):
    rng = np.random.default_rng(sq + sk)
    bh = 3
    q = jnp.asarray(rng.normal(0, 1, (bh, sq, d)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (bh, sk, d)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (bh, sk, d)), dtype)
    from repro.kernels.flash import kernel as K
    out = K.flash_fwd(q, k, v, causal=causal, window=window,
                      block_q=32, block_k=32)
    ref = fl_ref.flash_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_ops_gqa_layout():
    """Model-layout wrapper (GQA expand) vs the model's exact sdpa."""
    from repro.models import attention
    rng = np.random.default_rng(11)
    b, s, hq, hkv, dh = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (b, s, hq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, s, hkv, dh)), jnp.float32)
    out = fl_ops.flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=32)
    mask = attention._mask(s, s, True, None)
    ref = attention.sdpa(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

"""Backend-dispatch parity + tuning-cache behaviour (ISSUE-9).

The dispatch contract is that the backend choice is a pure performance
knob: every op produces bit-identical results under every backend that
``available_backends()`` offers, so the wire format can never depend on
which kernel happened to run. Three layers pin that down here:

  * **golden parity** - re-encoding the committed ``tests/golden/``
    fixtures under each pinned backend must reproduce the committed
    blobs hex-for-hex (the strongest end-to-end form of the claim);
  * **op-level fuzz** - seeded random workloads through the dispatched
    ops, each backend against the ``ref.py`` oracle, full stack state
    compared bit-for-bit (a fast subset of the deep sweep in
    ``tests/test_parity_fuzz.py``);
  * **tuning cache** - cold miss -> measured ``autotune_op`` ->
    persisted JSON -> warm ``lookup``/``resolve`` hit, plus the
    corrupt/stale/foreign-backend fallbacks that guarantee tuning
    state can never break coding.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ans
from repro.kernels import dispatch, tuning
from repro.kernels.ans import ops as ans_ops, ref as ans_ref
from repro.kernels.bucketize import ops as bk_ops, ref as bk_ref

BACKENDS = dispatch.available_backends()


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the tuning cache at a throwaway file for the test body."""
    path = str(tmp_path / "tuning_cache.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    tuning.refresh()
    yield path
    tuning.refresh()


# ---------------------------------------------------------------------------
# golden parity: wire bytes are backend-independent, end to end
# ---------------------------------------------------------------------------

def _committed(name: str) -> bytes:
    from tests.golden.make_golden import GOLDEN_DIR
    with open(os.path.join(GOLDEN_DIR, f"{name}.bin"), "rb") as f:
        return f.read()


@pytest.mark.parametrize("name", ["bbx1_uniform", "bbx1_vae_fixedpoint",
                                  "bbx2_stream"])
def test_golden_bytes_identical_under_every_backend(name):
    from tests.golden.make_golden import build
    encode, _decode, _data = build()[name]
    committed = _committed(name)
    for backend in BACKENDS:
        with dispatch.use_backend(backend):
            fresh = encode()
        assert fresh.hex() == committed.hex(), (
            f"{name} under backend={backend}: wire bytes diverged from "
            "the committed golden blob - the backend choice must never "
            "change the format")


def test_golden_decode_under_every_backend():
    from tests.golden.make_golden import build
    name = "bbx1_vae_fixedpoint"
    _encode, decode, data = build()[name]
    blob = _committed(name)
    for backend in BACKENDS:
        with dispatch.use_backend(backend):
            out = decode(blob)
        assert bool(jnp.array_equal(jnp.asarray(out),
                                    jnp.asarray(data))), (
            f"{name} under backend={backend}: lossy decode")


# ---------------------------------------------------------------------------
# op-level fuzz: each backend vs the oracle, bit for bit
# ---------------------------------------------------------------------------

def _assert_stacks_equal(a, b, what):
    for field in ("head", "buf", "ptr", "underflows", "overflows"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=f"{what}: stack.{field} diverged")


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_push_pop_parity_across_backends(seed):
    rng = np.random.default_rng(seed)
    steps, lanes, alphabet, precision = 6, 16, 11, 12
    probs = rng.dirichlet(np.ones(alphabet), size=lanes)
    table = ans.probs_to_starts(jnp.asarray(probs, jnp.float32),
                                precision)
    syms = jnp.asarray(rng.integers(0, alphabet, (steps, lanes)),
                       jnp.int32)
    stack = ans.make_stack(lanes, steps + 8,
                           key=jax.random.PRNGKey(seed))

    ref_full = ans_ref.push_many_table_ref(stack, table, syms, precision)
    for backend in BACKENDS:
        full = ans_ops.push_many_table(stack, table, syms, precision,
                                       backend=backend)
        _assert_stacks_equal(full, ref_full,
                             f"push_many_table[{backend}]")
        out, popped = ans_ops.pop_many(full, table, steps, precision,
                                       backend=backend)
        out_r, popped_r = ans_ref.pop_many_ref(ref_full, table, steps,
                                               precision)
        np.testing.assert_array_equal(np.asarray(popped),
                                      np.asarray(popped_r))
        _assert_stacks_equal(out, out_r, f"pop_many[{backend}]")


@pytest.mark.parametrize("seed", [3, 11])
def test_grid_pop_and_bucketize_parity_across_backends(seed):
    rng = np.random.default_rng(seed)
    lanes, steps, lat_bits, precision = 8, 5, 6, 12
    stack = ans.seed_stack(
        ans.make_stack(lanes, capacity=4 * steps,
                       key=jax.random.PRNGKey(seed)),
        jax.random.PRNGKey(seed + 1), n_chunks=2 * steps)
    mu = jnp.asarray(rng.normal(0, 1, (steps, lanes)), jnp.float32)
    sigma = jnp.asarray(rng.uniform(0.3, 1.5, (steps, lanes)),
                        jnp.float32)
    ref = ans_ref.pop_many_grid_ref(stack, "gaussian", mu, sigma, steps,
                                    lat_bits, precision)
    slot = jnp.asarray(rng.integers(0, 1 << precision, lanes),
                       jnp.uint32)
    bk_r = bk_ref.bucketize_ref(slot, mu[0], sigma[0], lat_bits,
                                precision)
    for backend in BACKENDS:
        out = ans_ops.pop_many_grid(stack, "gaussian", mu, sigma, steps,
                                    lat_bits, precision, backend=backend)
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(ref[1]),
                                      err_msg=f"grid syms [{backend}]")
        _assert_stacks_equal(out[0], ref[0],
                             f"pop_many_grid[{backend}]")
        bk = bk_ops.bucketize(slot, mu[0], sigma[0], lat_bits,
                              precision, backend=backend)
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(bk_r),
                                      err_msg=f"bucketize [{backend}]")


# ---------------------------------------------------------------------------
# tuning cache: round trip, resolve integration, corruption fallbacks
# ---------------------------------------------------------------------------

def test_tuning_cache_round_trip(tmp_cache):
    plat = dispatch.platform()
    assert tuning.lookup(plat, "push_many", lanes=8) is None   # cold
    decision = tuning.autotune_op("push_many", lanes=8, steps=4, reps=1)
    assert decision.backend in BACKENDS
    assert os.path.exists(tmp_cache)
    with open(tmp_cache) as f:
        raw = json.load(f)
    assert raw["version"] == tuning.CACHE_VERSION
    assert tuning.lookup(plat, "push_many", lanes=8) == decision  # warm
    # Bucketing: any lanes in the same power-of-two class hits too.
    assert tuning.lookup(plat, "push_many", lanes=5) == decision
    # resolve() consults the cache when nothing pins a backend.
    assert dispatch.resolve("push_many", lanes=8) == decision


def test_resolve_precedence_beats_cache(tmp_cache, monkeypatch):
    plat = dispatch.platform()
    cached = dispatch.Decision("xla", unroll=4)
    tuning.record(plat, "push_many", cached, 1.0, lanes=8)
    with dispatch.use_backend("interpret"):   # context over cache
        assert dispatch.resolve("push_many", lanes=8).backend == \
            "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")  # env too
    assert dispatch.resolve("push_many", lanes=8).backend == "interpret"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert dispatch.resolve("push_many", lanes=8) == cached


def test_interpret_is_never_cached_or_resolved_implicitly(tmp_cache):
    """The tuning cache refuses ``interpret``, ignores a hand-written
    ``interpret`` entry, and the heuristic never picks it."""
    plat = dispatch.platform()
    with pytest.raises(ValueError, match="interpret"):
        tuning.record(plat, "push_many", dispatch.Decision("interpret"),
                      1.0, lanes=8)
    tuning.record(plat, "push_many", dispatch.Decision("xla"), 1.0,
                  lanes=8)
    with open(tmp_cache) as f:
        raw = json.load(f)
    for entry in raw["entries"].values():
        entry["backend"] = "interpret"
    with open(tmp_cache, "w") as f:
        json.dump(raw, f)
    tuning.refresh()
    assert tuning.lookup(plat, "push_many", lanes=8) is None
    assert dispatch.resolve("push_many", lanes=8).backend != "interpret"
    for plat_name in ("cpu", "tpu"):
        assert dispatch.available_backends(plat_name)[0] != "interpret"
    assert dispatch.available_backends("tpu")[0] == "pallas"


def test_autotune_raises_when_a_candidate_fails(tmp_cache, monkeypatch):
    """A candidate that cannot run is an error, never a silent skip
    (and never an ``interpret`` fallback)."""
    def broken(*args, **kwargs):
        raise RuntimeError("lowering refused")
    monkeypatch.setattr(tuning, "_time_ms", broken)
    with pytest.raises(RuntimeError, match="lowering refused"):
        tuning.autotune_op("push_many", lanes=8, steps=4, reps=1)
    assert tuning.lookup(dispatch.platform(), "push_many", lanes=8) is None


@pytest.mark.parametrize("content", [
    "not json at all {{{",
    json.dumps({"version": -5, "entries": {"x": {}}}),   # stale version
    json.dumps(["wrong", "shape"]),
])
def test_corrupt_or_stale_cache_reads_as_empty(tmp_cache, content):
    with open(tmp_cache, "w") as f:
        f.write(content)
    tuning.refresh()
    plat = dispatch.platform()
    assert tuning.lookup(plat, "push_many", lanes=8) is None
    # The heuristic still resolves - tuning state can't break coding.
    assert dispatch.resolve("push_many", lanes=8).backend == \
        dispatch.available_backends()[0]
    # record() over the corrupt file leaves a clean, loadable cache.
    tuning.record(plat, "push_many", dispatch.Decision("xla"), 2.5,
                  lanes=8)
    tuning.refresh()
    assert tuning.lookup(plat, "push_many", lanes=8) == \
        dispatch.Decision("xla")


def test_cache_entry_naming_unavailable_backend_is_ignored(tmp_cache):
    plat = dispatch.platform()
    tuning.record(plat, "push_many", dispatch.Decision("xla"), 1.0,
                  lanes=8)
    # Hand-edit the persisted entry to a backend this platform can't
    # run (pallas-compiled on CPU): lookup must skip it, not crash.
    with open(tmp_cache) as f:
        raw = json.load(f)
    for entry in raw["entries"].values():
        entry["backend"] = "pallas"
    with open(tmp_cache, "w") as f:
        json.dump(raw, f)
    tuning.refresh()
    if "pallas" not in dispatch.available_backends():
        assert tuning.lookup(plat, "push_many", lanes=8) is None


def test_cli_multi_lane_sweep(tmp_cache, capsys):
    rc = tuning.main(["--lanes", "4", "8", "--ops", "push_many",
                      "--steps", "2", "--reps", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "lanes=4:" in out and "lanes=8:" in out
    plat = dispatch.platform()
    assert tuning.lookup(plat, "push_many", lanes=4) is not None
    assert tuning.lookup(plat, "push_many", lanes=8) is not None

"""Training substrate: optimizers, trainer, checkpointing, fault
tolerance (restart determinism), gradient compression."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfg_base
from repro.data import pipeline, tokens as tok_data
from repro.optim import adafactor, adamw, grad_compress
from repro.train import checkpoint, fault, trainer


def _tiny_cfg():
    return dataclasses.replace(
        cfg_base.reduced(cfg_base.get("smollm-360m")), vocab=64)


def _quadratic_params():
    return {"w": jnp.asarray([1.0, -2.0, 3.0]), "b": jnp.asarray(0.5)}


@pytest.mark.parametrize("opt_name", ["adamw", "adafactor"])
def test_optimizers_reduce_quadratic(opt_name):
    if opt_name == "adamw":
        opt = adamw.AdamW(learning_rate=adamw.constant_lr(0.1))
    else:
        opt = adafactor.Adafactor(
            learning_rate=adamw.constant_lr(0.3))
    params = {"w": jnp.asarray([[1.0, -2.0], [3.0, 0.5]]),
              "b": jnp.asarray([0.5, -1.0])}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    l0 = float(loss(params))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state = opt.update(grads, state, params)
    assert float(loss(params)) < 0.1 * l0


def test_train_step_reduces_lm_loss():
    cfg = _tiny_cfg()
    opt = trainer.make_optimizer(cfg, lr=3e-3, total_steps=40)
    state = trainer.init_state(jax.random.PRNGKey(0), cfg, opt)
    toks, _ = tok_data.markov_corpus(4000, vocab=cfg.vocab, seed=0)
    batch_fn = pipeline.lm_batch_fn(toks, batch=8, seq=32)
    step = jax.jit(trainer.make_train_step(cfg, opt))
    losses = []
    for i in range(30):
        batch = jax.tree_util.tree_map(jnp.asarray, batch_fn(0, i, 0, 1))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses


def test_grad_accumulation_matches_full_batch():
    """Mean of microbatch grads == full-batch grads (up to bf16 reduction
    order). Compared at the gradient level: Adam's first-step
    sign-normalization would amplify sub-ulp sign flips into O(lr) param
    diffs, which is not what this test is about."""
    from repro.models import transformer
    cfg = _tiny_cfg()
    params = transformer.init(jax.random.PRNGKey(1), cfg)
    toks, _ = tok_data.markov_corpus(2000, vocab=cfg.vocab, seed=1)
    batch = jax.tree_util.tree_map(
        jnp.asarray, pipeline.lm_batch_fn(toks, 8, 32)(0, 0, 0, 1))

    def grads_of(b):
        return jax.grad(
            lambda p: transformer.loss_fn(p, cfg, b)[0])(params)

    g_full = grads_of(batch)
    micro = jax.tree_util.tree_map(
        lambda x: x.reshape((4, 2) + x.shape[1:]), batch)
    g_acc = None
    for i in range(4):
        g_i = grads_of(jax.tree_util.tree_map(lambda x: x[i], micro))
        g_acc = g_i if g_acc is None else jax.tree_util.tree_map(
            jnp.add, g_acc, g_i)
    g_acc = jax.tree_util.tree_map(lambda g: g / 4, g_acc)
    for a, b in zip(jax.tree_util.tree_leaves(g_full),
                    jax.tree_util.tree_leaves(g_acc)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(np.abs(a).max(), 1e-6)
        np.testing.assert_allclose(a, b, atol=3e-2 * scale, rtol=0.1)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    checkpoint.save(7, tree, str(tmp_path))
    assert checkpoint.latest_step(str(tmp_path)) == 7
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    out = checkpoint.restore(like, str(tmp_path))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_pruning(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(s, tree, str(tmp_path), keep=2)
    assert checkpoint.all_steps(str(tmp_path)) == [4, 5]


def test_restart_determinism(tmp_path):
    """Training with injected node failures must produce bitwise-identical
    results to an uninterrupted run (the core fault-tolerance claim)."""
    cfg = _tiny_cfg()
    opt = adamw.AdamW(learning_rate=adamw.constant_lr(1e-3))
    toks, _ = tok_data.markov_corpus(2000, vocab=cfg.vocab, seed=2)
    raw_batch_fn = pipeline.lm_batch_fn(toks, 4, 16)
    step = jax.jit(trainer.make_train_step(cfg, opt))

    def init_fn():
        return trainer.init_state(jax.random.PRNGKey(3), cfg, opt)

    def batch_fn(s):
        return jax.tree_util.tree_map(jnp.asarray, raw_batch_fn(0, s, 0, 1))

    clean_dir, faulty_dir = str(tmp_path / "clean"), str(tmp_path / "faulty")
    clean, r0 = fault.run_training(
        init_fn=init_fn, step_fn=step, batch_fn=batch_fn, n_steps=12,
        ckpt_dir=clean_dir, save_every=4)
    assert r0 == 0

    fail_at = {3, 9}

    def injector(s):
        if s in fail_at:
            fail_at.discard(s)
            raise fault.SimulatedNodeFailure(f"node lost at {s}")

    faulty, r1 = fault.run_training(
        init_fn=init_fn, step_fn=step, batch_fn=batch_fn, n_steps=12,
        ckpt_dir=faulty_dir, save_every=4, failure_injector=injector)
    assert r1 == 2
    for a, b in zip(jax.tree_util.tree_leaves(clean.params),
                    jax.tree_util.tree_leaves(faulty.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_flags_straggler():
    wd = fault.StepWatchdog(z_threshold=3.0, warmup=3)
    for s in range(10):
        wd.observe(s, 0.1 + 0.001 * (s % 2))
    wd.observe(10, 5.0)
    assert 10 in wd.report.stragglers


def test_grad_compression_error_feedback():
    """Compressed-gradient training stays close to exact; wire size well
    under 8 bits/param."""
    rng = np.random.default_rng(5)
    # Heavy-tailed grads (the realistic case: typical |g| << max |g|, so
    # int8 symbols concentrate near zero and entropy-code well).
    w = rng.normal(0, 1e-3, (256, 256))
    outliers = rng.random((256, 256)) < 0.01
    w = np.where(outliers, w * 25, w).astype(np.float32)
    grads = {"w": jnp.asarray(w),
             "b": jnp.asarray(rng.normal(0, 1e-3, (64,)), jnp.float32)}
    cstate = grad_compress.init_state(grads)
    out, cstate = grad_compress.compress_grads(grads, cstate)
    # Error feedback: residual equals g - deq exactly.
    err = np.asarray(cstate.error["w"])
    diff = np.asarray(grads["w"]) - np.asarray(out["w"])
    np.testing.assert_allclose(err, diff, atol=1e-7)
    # Relative error bounded by the int8 step.
    rel = np.abs(diff).max() / np.abs(np.asarray(grads["w"])).max()
    assert rel < 1.2 / 127
    bits_total, bits_pp = grad_compress.measure_wire_bits(grads, cstate)
    assert bits_pp < 8.5, bits_pp


def test_elastic_restore_resharding(tmp_path):
    """A checkpoint written from one topology restores onto another
    (here: host -> explicit single-device sharding)."""
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    checkpoint.save(1, tree, str(tmp_path))
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out = checkpoint.restore(jax.tree_util.tree_map(jnp.zeros_like, tree),
                             str(tmp_path), shardings=shardings)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding == shardings["w"]


def test_adafactor_chunked_matches_unchunked():
    """The two-pass chunked update (big stacked leaves) is bit-for-bit the
    same math as the direct path."""
    rng = np.random.default_rng(9)
    p_small = {"w": jnp.asarray(rng.normal(0, 0.1, (4, 32, 16)),
                                jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(0, 0.01, (4, 32, 16)), jnp.float32)}
    base = adafactor.Adafactor(learning_rate=adamw.constant_lr(0.01))
    # Force the chunked path by monkeypatching the threshold.
    old = adafactor.Adafactor.CHUNK_THRESHOLD
    try:
        s1 = base.init(p_small)
        p1, _ = base.update(g, s1, p_small)
        adafactor.Adafactor.CHUNK_THRESHOLD = 1
        s2 = base.init(p_small)
        p2, _ = base.update(g, s2, p_small)
    finally:
        adafactor.Adafactor.CHUNK_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-6, atol=1e-7)

"""Mixture-of-Experts block: top-k routing, capacity-bounded sort dispatch,
expert-parallel all_to_all (DeepSeek/Switch-style), shared-expert and
dense-parallel (Arctic) variants.

Three execution paths sharing one routing implementation:

  * ``dense``    - every expert computes every token, one-hot combine.
                   O(E) FLOPs: correctness oracle + tiny smoke configs only.
  * ``local``    - capacity-bucketed sort dispatch on one device (EP=1).
  * ``ep``       - shard_map over the 'model' axis: tokens are
                   sequence-split across EP ranks, scatter-packed into
                   [E, C, D] buckets, exchanged with all_to_all, FFN'd by
                   the local experts, exchanged back, combined, and
                   all-gathered back to the full sequence. 2x all_to_all +
                   1x all_gather per layer - the production schedule.

Routing is identical across paths (argsort-based, deterministic), so
``dense`` == ``local`` == ``ep`` exactly whenever no token is dropped;
tests assert this.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers
from repro.sharding import api as shard_api

ROUTER_Z_COEF = 1e-3
LOAD_BALANCE_COEF = 1e-2


def moe_init(key, cfg):
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    scale = 1.0 / d ** 0.5
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, e)) *
                         scale).astype(jnp.float32)},
        "wi": (jax.random.normal(ks[1], (e, d, f)) * scale
               ).astype(jnp.float32),
        "wg": (jax.random.normal(ks[2], (e, d, f)) * scale
               ).astype(jnp.float32),
        "wo": (jax.random.normal(ks[3], (e, f, d)) * (1.0 / f ** 0.5)
               ).astype(jnp.float32),
    }
    if cfg.shared_expert:
        p["shared"] = layers.mlp_init(ks[4], d, cfg.expert_d_ff, "silu")
    if cfg.dense_ff_parallel:
        p["dense_mlp"] = layers.mlp_init(ks[5], d, cfg.d_ff, "silu")
    return p


def route(router_p, x, cfg, compute_dtype=jnp.bfloat16):
    """x [..., D] -> (gates [..., K], experts int32 [..., K], aux_loss)."""
    logits = layers.dense(router_p, x, jnp.float32)  # router in f32
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Aux losses: Switch-style load balance + router z-loss.
    e = cfg.n_experts
    density = jnp.mean(
        jax.nn.one_hot(experts[..., 0], e, dtype=jnp.float32),
        axis=tuple(range(experts.ndim - 1)))
    density_prob = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    lb = jnp.sum(density * density_prob) * e * LOAD_BALANCE_COEF
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * ROUTER_Z_COEF
    return gates.astype(compute_dtype), experts.astype(jnp.int32), lb + z


def _expert_ffn(wi, wg, wo, xs, compute_dtype):
    """xs [E, C, D] through per-expert gated MLP -> [E, C, D]."""
    h = jnp.einsum("ecd,edf->ecf", xs, wi.astype(compute_dtype))
    g = jnp.einsum("ecd,edf->ecf", xs, wg.astype(compute_dtype))
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(compute_dtype))


def _dispatch_indices(experts: jnp.ndarray, n_experts: int,
                      capacity: int):
    """Deterministic capacity-bounded slots via stable argsort.

    experts int32[T, K] -> (flat token index [T*K], expert id [T*K],
    slot [T*K], keep mask [T*K]).
    """
    t, k = experts.shape
    eid = experts.reshape(-1)
    order = jnp.argsort(eid, stable=True)           # group by expert
    eid_sorted = eid[order]
    counts = jnp.bincount(eid, length=n_experts)
    starts = jnp.cumsum(counts) - counts            # exclusive prefix
    slot_sorted = jnp.arange(t * k) - starts[eid_sorted]
    keep_sorted = slot_sorted < capacity
    # Un-sort back to assignment order.
    inv = jnp.argsort(order, stable=True)
    slot = slot_sorted[inv]
    keep = keep_sorted[inv]
    tok = jnp.repeat(jnp.arange(t), k)
    return tok, eid, slot.astype(jnp.int32), keep


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor /
            max(cfg.n_experts, 1))
    return max(c, cfg.top_k)


def _moe_tokens_local(xf, gates, experts, wi, wg, wo, capacity,
                      cfg, compute_dtype):
    """Single-rank capacity dispatch. xf [T, D] -> [T, D]."""
    t, d = xf.shape
    e = wi.shape[0]
    tok, eid, slot, keep = _dispatch_indices(experts, e, capacity)
    # Pack: buffer [E, C, D].
    safe_e = jnp.where(keep, eid, e)     # OOB row -> dropped
    buf = jnp.zeros((e + 1, capacity, d), compute_dtype)
    buf = buf.at[safe_e, slot].set(xf[tok], mode="drop")
    out_buf = _expert_ffn(wi, wg, wo, buf[:e], compute_dtype)
    # Unpack + gate-weighted combine.
    gathered = out_buf[jnp.where(keep, eid, 0), slot]  # [T*K, D]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    gflat = gates.reshape(-1)[:, None].astype(compute_dtype)
    out = jnp.zeros((t, d), compute_dtype).at[tok].add(gathered * gflat)
    return out


def moe_apply_dense(p, x, cfg, compute_dtype=jnp.bfloat16):
    """O(E) oracle: all experts on all tokens, one-hot combine."""
    gates, experts, aux = route(p["router"], x, cfg, compute_dtype)
    xf = x.reshape(-1, x.shape[-1]).astype(compute_dtype)
    h = jnp.einsum("td,edf->tef", xf, p["wi"].astype(compute_dtype))
    g = jnp.einsum("td,edf->tef", xf, p["wg"].astype(compute_dtype))
    h = jax.nn.silu(h) * g
    yall = jnp.einsum("tef,efd->ted", h, p["wo"].astype(compute_dtype))
    onehot = jax.nn.one_hot(experts.reshape(xf.shape[0], -1),
                            cfg.n_experts, dtype=compute_dtype)
    combine = jnp.einsum("tk,tke->te", gates.reshape(xf.shape[0], -1),
                         onehot)
    out = jnp.einsum("te,ted->td", combine, yall)
    return _finish(p, x, out.reshape(x.shape), cfg, compute_dtype), aux


def _finish(p, x, moe_out, cfg, compute_dtype):
    if cfg.shared_expert:
        moe_out = moe_out + layers.mlp_apply(p["shared"], x, "silu",
                                             compute_dtype)
    if cfg.dense_ff_parallel:
        moe_out = moe_out + layers.mlp_apply(p["dense_mlp"], x, "silu",
                                             compute_dtype)
    return moe_out


def moe_apply(p, x, cfg, compute_dtype=jnp.bfloat16):
    """Production path: EP all_to_all when a mesh with a >1 'model' axis is
    active, local capacity dispatch otherwise. x [B, S, D]."""
    mesh = shard_api.current_mesh()
    ep = mesh.shape.get("model", 1) if mesh is not None else 1
    if ep > 1:
        return _moe_apply_ep(p, x, cfg, mesh, compute_dtype)
    gates, experts, aux = route(p["router"], x, cfg, compute_dtype)
    xf = x.reshape(-1, x.shape[-1]).astype(compute_dtype)
    cap = _capacity(xf.shape[0], cfg)
    out = _moe_tokens_local(xf, gates.reshape(xf.shape[0], -1),
                            experts.reshape(xf.shape[0], -1),
                            p["wi"], p["wg"], p["wo"], cap, cfg,
                            compute_dtype)
    return _finish(p, x, out.reshape(x.shape), cfg, compute_dtype), aux


def _moe_apply_ep(p, x, cfg, mesh, compute_dtype):
    """shard_map EP: flattened tokens are split across the 'model' axis
    (works for train, prefill AND single-token decode), packed into
    capacity buckets, exchanged with all_to_all, FFN'd by local experts,
    exchanged back, combined, and all-gathered. Requires E % ep == 0."""
    b, s, d = x.shape
    ep = mesh.shape["model"]
    e = cfg.n_experts
    assert e % ep == 0, (e, ep)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    n_tok_loc = (b // dp_size) * s       # tokens per data shard
    t_pad = -(-n_tok_loc // ep) * ep     # padded to a multiple of ep
    t_loc = t_pad // ep
    cap = _capacity(t_loc, cfg)

    def inner(xb, router_w, wi, wg, wo):
        # xb [B_loc, S, D] replicated over model; take this rank's tokens.
        idx = jax.lax.axis_index("model")
        xflat = xb.reshape(-1, d)
        if t_pad != n_tok_loc:
            xflat = jnp.pad(xflat, ((0, t_pad - n_tok_loc), (0, 0)))
        xf = jax.lax.dynamic_slice_in_dim(xflat, idx * t_loc, t_loc, 0)
        xf = xf.astype(compute_dtype)      # [T_loc, D]
        gates, experts, aux = route({"w": router_w}, xf, cfg, compute_dtype)
        tok, eid, slot, keep = _dispatch_indices(experts, e, cap)
        safe_e = jnp.where(keep, eid, e)
        buf = jnp.zeros((e + 1, cap, d), compute_dtype)
        buf = buf.at[safe_e, slot].set(xf[tok], mode="drop")[:e]
        # Exchange: [E, C, D] -> [ep, E_loc, C, D] -> a2a -> [ep(src), ...]
        e_loc = e // ep
        buf = buf.reshape(ep, e_loc, cap, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        # [ep_src, E_loc, C, D]: all ranks' tokens for my experts.
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)
        out = _expert_ffn(wi, wg, wo, buf, compute_dtype)
        # Inverse exchange.
        out = out.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        out = out.reshape(e, cap, d)
        gathered = out[jnp.where(keep, eid, 0), slot]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        gflat = gates.reshape(-1)[:, None].astype(compute_dtype)
        yc = jnp.zeros_like(xf).at[tok].add(gathered * gflat)
        # Reassemble all token chunks across EP ranks.
        y = jax.lax.all_gather(yc, "model", axis=0, tiled=True)  # [T_pad,D]
        y = y[:n_tok_loc].reshape(xb.shape)
        return y, jax.lax.pmean(aux, "model")

    wi_spec = P("model", None, None)
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  wi_spec, wi_spec, wi_spec),
        out_specs=(P(dp_axes, None, None), P()), check_vma=False,
    )(x, p["router"]["w"], p["wi"], p["wg"], p["wo"])
    y, aux = out
    return _finish(p, x, y, cfg, compute_dtype), aux

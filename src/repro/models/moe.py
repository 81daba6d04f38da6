"""Mixture-of-Experts FFN with shard_map-local dispatch (EP or TP).

Two sharding modes, chosen per config (DESIGN.md §5):

  * ``ep`` — experts sharded over the ``model`` axis (llama4-scout: 16e on a
    16-wide axis).  Activations are replicated over ``model`` (they are only
    batch-sharded), so each shard simply computes its *local* experts on the
    tokens routed to them and a single psum('model') combines — the same
    psum a row-parallel TP matmul needs, i.e. EP here costs no extra
    collective.
  * ``tp`` — every shard holds all experts with the hidden dim sliced
    (qwen2: 60e x 1408; 60 % 16 != 0 so EP would imbalance).  One
    psum('model') after the down-projection.

Dispatch is capacity-based and *local to the shard* (no global sort): the
position of each token within its expert's buffer is a cumsum over the local
one-hot assignment matrix.  Overflowing tokens are dropped (their combine
weight is zero), matching capacity-factor semantics of production MoE stacks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import ShardCtx


def init_moe_params(key, cfg: ArchConfig, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s).astype(jnp.float32),
        "we_gate": (jax.random.normal(ks[1], (e, d, f)) * s).astype(dtype),
        "we_up": (jax.random.normal(ks[2], (e, d, f)) * s).astype(dtype),
        "we_down": (jax.random.normal(ks[3], (e, f, d))
                    * f ** -0.5).astype(dtype),
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        p["ws_gate"] = (jax.random.normal(ks[4], (d, fs)) * s).astype(dtype)
        p["ws_up"] = (jax.random.normal(ks[5], (d, fs)) * s).astype(dtype)
        p["ws_down"] = (jax.random.normal(ks[6], (fs, d))
                        * fs ** -0.5).astype(dtype)
    return p


def moe_mode(cfg: ArchConfig, tp_size: int) -> str:
    return "ep" if cfg.num_experts % tp_size == 0 else "tp"


def moe_param_specs(cfg: ArchConfig, tp_size: int):
    mode = moe_mode(cfg, tp_size)
    if mode == "ep":
        expert = {"we_gate": P("model", None, None),
                  "we_up": P("model", None, None),
                  "we_down": P("model", None, None)}
    else:
        expert = {"we_gate": P(None, None, "model"),
                  "we_up": P(None, None, "model"),
                  "we_down": P(None, "model", None)}
    p = {"router": P(None, None), **expert}
    if cfg.num_shared_experts:
        p.update({"ws_gate": P(None, "model"),
                  "ws_up": P(None, "model"),
                  "ws_down": P("model", None)})
    return p


def _local_moe(x, router, wg, wu, wd, *, cfg: ArchConfig, mode: str,
               tp_axis: str, capacity_factor: float):
    """Per-shard MoE compute.  x: (N, d) local tokens; weights local slices."""
    n, d = x.shape
    e = cfg.num_experts
    k = cfg.top_k
    e_local = wg.shape[0]

    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), router)
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)          # (n, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # local capacity per expert
    cap = max(8, int(k * n * capacity_factor) // e)

    # one-hot over experts for each of the k assignments -> position via cumsum
    flat_e = top_idx.reshape(-1)                          # (n*k,)
    flat_w = top_vals.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)   # (n*k, e)
    pos = jnp.cumsum(onehot, axis=0) - onehot             # positions start at 0
    mypos = jnp.sum(pos * onehot, axis=-1)                # (n*k,)
    keep = mypos < cap

    if mode == "ep":
        shard = jax.lax.axis_index(tp_axis)
        base = shard * e_local
        local = (flat_e >= base) & (flat_e < base + e_local)
        keep = keep & local
        local_e = flat_e - base
    else:
        local_e = flat_e

    tok = jnp.arange(n * k) // k
    safe_e = jnp.where(keep, local_e, 0)
    safe_p = jnp.where(keep, mypos, cap - 1)

    # gather tokens into (e_local, cap, d) buffers
    xe = jnp.zeros((e_local, cap, d), x.dtype)
    xe = xe.at[safe_e, safe_p].add(
        jnp.where(keep[:, None], x[tok], 0).astype(x.dtype))

    # expert FFN (SwiGLU)
    g = jnp.einsum("ecd,edf->ecf", xe, wg)
    u = jnp.einsum("ecd,edf->ecf", xe, wu)
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, wd)                # (e_local, cap, d)

    # combine back: weighted scatter-add into token rows
    contrib = ye[safe_e, safe_p] * jnp.where(keep, flat_w, 0.0)[:, None]
    y = jnp.zeros_like(x).at[tok].add(contrib.astype(x.dtype))

    # aux load-balance loss terms (local sums; caller psums over dp)
    me = jnp.mean(gates, axis=0)                          # (e,)
    ce = jnp.mean(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)
    return y, aux


def moe_ffn(x, p, cfg: ArchConfig, ctx: Optional[ShardCtx],
            capacity_factor: float | None = None, tips_important=None):
    """(B, T, d) -> (B, T, d) mixture-of-experts FFN (+ shared experts)."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    if tips_important is not None:
        from repro.core import tips as tips_mod
        x = tips_mod.apply_precision_mask(x, tips_important)

    b, t, d = x.shape
    if ctx is None:
        # single-device path (smoke tests): same math, one "shard"
        y, aux = _local_moe(x.reshape(-1, d), p["router"], p["we_gate"],
                            p["we_up"], p["we_down"], cfg=cfg, mode="tp",
                            tp_axis=None, capacity_factor=capacity_factor)
        y = y.reshape(b, t, d)
    else:
        mode = moe_mode(cfg, ctx.tp_size)
        specs = moe_param_specs(cfg, ctx.tp_size)
        dp = ctx.dp_axes

        def body(xl, router, wg, wu, wd):
            n = xl.shape[0] * xl.shape[1]
            y, aux = _local_moe(xl.reshape(n, d), router, wg, wu, wd,
                                cfg=cfg, mode=mode, tp_axis=ctx.tp_axis,
                                capacity_factor=capacity_factor)
            y = jax.lax.psum(y, ctx.tp_axis) if mode == "ep" else \
                jax.lax.psum(y, ctx.tp_axis)
            aux = jax.lax.pmean(aux, dp)
            return y.reshape(xl.shape), aux

        y, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(dp, None, None), specs["router"], specs["we_gate"],
                      specs["we_up"], specs["we_down"]),
            out_specs=(P(dp, None, None), P()),
            check_vma=False,
        )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])

    if cfg.num_shared_experts:
        g = jnp.einsum("btd,df->btf", x, p["ws_gate"])
        u = jnp.einsum("btd,df->btf", x, p["ws_up"])
        y = y + jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u, p["ws_down"])
    return y, aux

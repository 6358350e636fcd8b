"""Mixture-of-Experts FFN with sort-based capacity dispatch (GShard-style).

TPU adaptation: no ragged tensors — tokens are routed to a fixed
(E, C, d) buffer via a sort + rank-in-expert computation so every shape
is static.  Tokens beyond an expert's capacity C are dropped (their
residual passes through), the standard trade on TPU (Switch/GShard).

Expert weights are laid out (E, d, ff) and sharded expert-parallel along
the 'model' mesh axis (see dist/sharding.PARAM_RULES) — the dispatch
then lowers to an all-to-all over the expert dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical
from .config import ModelConfig
from .layers import init_rmsnorm, residual, rms_norm


def init_moe(key, cfg: ModelConfig):
    d, ffe, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    s_in, s_out = d ** -0.5, ffe ** -0.5
    return {
        "norm": init_rmsnorm(d),
        "router": (jax.random.normal(kr, (d, e)) * s_in).astype(jnp.float32),
        "experts_gate": (jax.random.normal(kg, (e, d, ffe)) * s_in).astype(dt),
        "experts_up": (jax.random.normal(ku, (e, d, ffe)) * s_in).astype(dt),
        "experts_down": (jax.random.normal(kd, (e, ffe, d)) * s_out).astype(dt),
    }


def _dispatch_one_group(h, logits, e, k, capacity):
    """Token dispatch within ONE group (a batch row): all sort/rank work is
    local to the group, so it shards cleanly over the data axis.

    h: (T, d); logits: (T, E).  Returns (buf (E, C, d), combine info)."""
    t, d = h.shape
    gates, experts = jax.lax.top_k(logits, k)               # (T, k)
    gates = jax.nn.softmax(gates, axis=-1).astype(h.dtype)

    flat_expert = experts.reshape(-1)                        # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)                # (T*k,)
    flat_gate = gates.reshape(-1)

    # rank within expert via sort (static shapes)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    seg_start = jnp.searchsorted(sorted_expert, jnp.arange(e))
    rank_sorted = jnp.arange(t * k) - seg_start[sorted_expert]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))

    keep = rank < capacity
    slot = jnp.where(keep, flat_expert * capacity + rank, e * capacity)
    buf = jnp.zeros((e * capacity, d), h.dtype)
    buf = buf.at[slot].set(h[flat_token], mode="drop")
    return buf.reshape(e, capacity, d), (slot, keep, flat_token, flat_gate)


def _combine_one_group(out_buf, info, t, d, dtype):
    slot, keep, flat_token, flat_gate = info
    flat = out_buf.reshape(-1, d)
    gathered = jnp.where(
        keep[:, None], flat.at[slot].get(mode="fill", fill_value=0), 0)
    return jnp.zeros((t, d), dtype).at[flat_token].add(
        gathered * flat_gate[:, None])


def moe_ffn(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x: (B, S, d) -> (B, S, d) with residual add.

    GShard-style GROUPED dispatch: each batch row is a dispatch group with
    its own capacity, so the sort/rank/scatter tensors keep the batch dim
    and stay sharded over the data axis (a global-token sort would force
    full replication under SPMD — measured 137 GB/device on the 235B
    config before this layout).  Expert weights are sharded over the
    model axis; GSPMD lowers the (group, expert) einsums to all-to-alls.
    """
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    capacity = max(int(s * k / e * cfg.moe_capacity_factor), 1)

    h = rms_norm(p["norm"], x, cfg.norm_eps)                 # (B, S, d)
    # dispatch must be LOCAL per batch row: pin h to batch-only sharding
    # (un-shard seq) so the scatter/gather of tokens into the expert
    # buffer never crosses a mesh axis — GSPMD otherwise replicates the
    # buffers via TB-scale all-reduces (measured: 3.2 TB/step on qwen3).
    h = logical(h, "batch", None, None)
    logits = jnp.einsum("bsd,de->bse", h.astype(jnp.float32), p["router"])

    buf, info = jax.vmap(
        lambda hh, ll: _dispatch_one_group(hh, ll, e, k, capacity)
    )(h, logits)                                             # buf (B,E,C,d)
    buf = logical(buf, "batch", "experts", None, None)

    gate_h = jnp.einsum("becd,edf->becf", buf, p["experts_gate"])
    up_h = jnp.einsum("becd,edf->becf", buf, p["experts_up"])
    act = jax.nn.silu(gate_h) * up_h
    out_buf = jnp.einsum("becf,efd->becd", act, p["experts_down"])
    out_buf = logical(out_buf, "batch", "experts", None, None)

    out = jax.vmap(
        lambda ob, inf: _combine_one_group(ob, inf, s, d, h.dtype)
    )(out_buf, info)
    out = logical(out, "batch", None, None)
    return residual(cfg, x, out)


def aux_load_balance_loss(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e (optional add-on)."""
    b, s, d = x.shape
    h = rms_norm(p["norm"], x, cfg.norm_eps).reshape(b * s, d)
    probs = jax.nn.softmax(h.astype(jnp.float32) @ p["router"], axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, cfg.moe_experts), axis=0)
    pmean = jnp.mean(probs, axis=0)
    return cfg.moe_experts * jnp.sum(f * pmean)

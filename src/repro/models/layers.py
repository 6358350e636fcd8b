"""Shared neural layers: norm, RoPE, GQA attention, FFN, losses.

Functional style: ``init_*`` returns a params dict; ``apply`` functions
are pure.  Activations carry logical sharding annotations
(repro.dist.sharding.logical) that are no-ops outside a mesh context.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical
from repro.kernels.flash_attention import gqa_attention, gqa_decode
from .config import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def residual(cfg: ModelConfig, x: jax.Array, branch: jax.Array) -> jax.Array:
    """``x + branch``, the branch scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (self / cross), with optional KV cache
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    dh, hq, hkv = cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    scale = d ** -0.5
    dt = _dtype(cfg)
    return {
        "norm": init_rmsnorm(d),
        "wq": (jax.random.normal(kq, (d, hq, dh)) * scale).astype(dt),
        "wk": (jax.random.normal(kk, (d, hkv, dh)) * scale).astype(dt),
        "wv": (jax.random.normal(kv, (d, hkv, dh)) * scale).astype(dt),
        "wo": (jax.random.normal(ko, (hq, dh, d)) * scale * 0.5).astype(dt),
    }


def attention(
    p,
    cfg: ModelConfig,
    x: jax.Array,                     # (B, S, d)
    positions: jax.Array,
    *,
    kv: Optional[jax.Array] = None,   # cross-attn memory (B, S_mem, d)
    cache: Optional[dict] = None,     # {"k","v","len"} decode cache
    causal: bool = True,
):
    """Returns (out, new_cache)."""
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    # one all-gather of the (seq-sharded) residual per attention block,
    # shared by the q/k/v projections — instead of one per einsum.
    h = logical(h, "batch", None, None)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    if cfg.attention_multiplier:
        # the kernels scale scores by d_head^-0.5: fold the rest into q
        q = q * (cfg.attention_multiplier * cfg.d_head ** 0.5)
    q = logical(q, "batch", None, "heads", None)
    src = h if kv is None else kv      # memory (e.g. image patch embeds)
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    k = logical(k, "batch", None, "heads", None)
    v = logical(v, "batch", None, "heads", None)

    # cross-attention (q-len != kv-len) takes the plain XLA path; the
    # flash kernel / chunked scan handle the self-attention hot spot.
    impl = cfg.attn_impl if kv is None else "ref"
    if cache is None or x.shape[1] > 1:
        # full-sequence path (training, or prefill writing into the cache)
        if kv is None and cfg.rope:   # self attention with rope
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        # the core alone, between the projections and ``wo``: its device
        # ops carry the scope in the trace (``train_attention_device_ms``)
        with jax.named_scope("attention"):
            if impl == "chunked":
                from .attention_xla import chunked_gqa_attention
                out = chunked_gqa_attention(
                    q, k, v, causal=causal and kv is None,
                    block_q=cfg.attn_block_q)
            else:
                out = gqa_attention(q, k, v, causal=causal and kv is None,
                                    use_pallas=impl == "pallas")
        new_cache = None
        if kv is None and cache is not None:
            s = x.shape[1]
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
                "len": jnp.full((x.shape[0],), s, jnp.int32),
            }
    else:
        # single-token decode: append to cache, flash-decode over it
        assert x.shape[1] == 1
        if cfg.rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        b = x.shape[0]
        idx = cache["len"]             # (B,) current lengths
        k_cache = cache["k"].at[jnp.arange(b), idx].set(k[:, 0])
        v_cache = cache["v"].at[jnp.arange(b), idx].set(v[:, 0])
        new_len = idx + 1
        out = gqa_decode(q, k_cache, v_cache, new_len,
                         use_pallas=impl == "pallas")
        new_cache = {"k": k_cache, "v": v_cache, "len": new_len}

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    out = logical(out, "batch", None, None)
    return residual(cfg, x, out), new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int):
    dt = _dtype(cfg)
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dt),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dt),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig):
    d, ff = cfg.d_model, cfg.d_ff
    kg, ku, kd = jax.random.split(key, 3)
    dt = _dtype(cfg)
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {
        "norm": init_rmsnorm(d),
        "w_up": (jax.random.normal(ku, (d, ff)) * s_in).astype(dt),
        "w_down": (jax.random.normal(kd, (ff, d)) * s_out).astype(dt),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = (jax.random.normal(kg, (d, ff)) * s_in).astype(dt)
    return p


def mlp(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    up = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    up = logical(up, "batch", None, "ff")
    if cfg.act == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
        act = jax.nn.silu(gate) * up
    elif cfg.act == "squared_relu":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    out = jnp.einsum("bsf,fd->bsd", act, p["w_down"])
    out = logical(out, "batch", None, None)
    return residual(cfg, x, out)


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    ke, kh = jax.random.split(key)
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "lm_head": (jax.random.normal(kh, (cfg.d_model, cfg.vocab))
                    * cfg.d_model ** -0.5).astype(dt),
        "final_norm": init_rmsnorm(cfg.d_model),
    }


def embed_tokens(p, tokens: jax.Array) -> jax.Array:
    return logical(p["embed"][tokens], "batch", None, None)


def lm_logits(p, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    h = rms_norm(p["final_norm"], h, cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, p["lm_head"])
    return logical(logits, "batch", None, "vocab")


def chunked_cross_entropy(
    p, cfg: ModelConfig, h: jax.Array, targets: jax.Array,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean next-token xent without materialising (B, S, V) logits.

    The (d -> vocab) projection + softmax run per sequence-chunk inside a
    remat'd scan so peak activation memory is B*chunk*V instead of B*S*V —
    the difference between fitting and not fitting 200k-vocab configs.
    """
    b, s, d = h.shape
    h = rms_norm(p["final_norm"], h, cfg.norm_eps)
    c = min(cfg.loss_chunk, s)
    if s % c != 0:
        c = s
    n_chunks = s // c
    hc = h.reshape(b, n_chunks, c, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_chunks, c).transpose(1, 0, 2)

    w = None if weights is None else weights.astype(jnp.float32)  # (B,)

    @jax.checkpoint
    def chunk_loss(carry, xs):
        hx, tx = xs                               # (B, c, d), (B, c)
        logits = jnp.einsum("bsd,dv->bsv", hx, p["lm_head"])
        logits = logical(logits, "batch", None, "vocab").astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tx[..., None], axis=-1)[..., 0]
        xent = logz - gold                        # (B, c)
        if w is not None:
            xent = xent * w[:, None]              # LGD importance weights
        return carry + jnp.sum(xent), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (hc, tc))
    return total / (b * s)

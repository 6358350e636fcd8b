"""Granite-3.0's dense decoder as the program runs it.

A pre-norm stack with one attention block and one MLP per layer: RMSNorm,
GQA attention with rotary positions over the whole head, a SwiGLU MLP, and
an untied head.  The program reads none of Granite's four multipliers, so a
file may give them only at the values that leave the stack as it is; the
same holds for a tied head and for biases.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..reference import F32, mm, rms

# HF config.json key -> ModelConfig field, for the keys the model reads
_MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
}
_ACTS = {"silu": "swiglu"}

# max_position_embeddings bounds the positions a sequence may take; with
# rope_scaling null it changes nothing the program computes
KEYS = (*_MODEL_KEYS, "hidden_act", "torch_dtype", "max_position_embeddings")
FIXED = {
    "tie_word_embeddings": False,
    "attention_bias": False,
    "mlp_bias": False,
    "rope_scaling": None,
    "embedding_multiplier": 1.0,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
    "attention_multiplier": lambda cfg: cfg.d_head ** -0.5,
}
ATTN_BLOCK = 512


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models import ModelConfig

    kw = {field: conf[key] for key, field in _MODEL_KEYS.items()
          if key in conf}
    kw["act"] = _ACTS[conf["hidden_act"]]
    kw["dtype"] = conf["torch_dtype"]
    kw.update(conf.get("execution", {}))
    return ModelConfig(name=name, **kw)


# -- weights ---------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _block(key, cfg):
    d, dh, hq, hkv, ff = (cfg.d_model, cfg.d_head, cfg.n_heads,
                          cfg.n_kv_heads, cfg.d_ff)
    dt = jnp.dtype(cfg.dtype)
    ka, _, kf = jax.random.split(key, 3)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    s = d ** -0.5
    ones = {"scale": jnp.ones((d,), jnp.float32)}
    kg, ku, kd = jax.random.split(kf, 3)
    return {
        "attn": {
            "norm": ones,
            "wq": _normal(kq, (d, hq, dh), s, dt),
            "wk": _normal(kk, (d, hkv, dh), s, dt),
            "wv": _normal(kv, (d, hkv, dh), s, dt),
            "wo": _normal(ko, (hq, dh, d), s * 0.5, dt),
        },
        "ffn": {
            "norm": ones,
            "w_up": _normal(ku, (d, ff), s, dt),
            "w_down": _normal(kd, (ff, d), ff ** -0.5, dt),
            "w_gate": _normal(kg, (d, ff), s, dt),
        },
    }


def make_params(key, cfg):
    """Weights of a dense SwiGLU decoder with one attention block per layer,
    exactly as the program's initialiser lays them out (same key splits,
    scales and dtype; layers stacked on axis 0)."""
    k_embed, k_blocks, _ = jax.random.split(key, 3)
    ke, kh = jax.random.split(k_embed)
    d, v = cfg.d_model, cfg.vocab
    dt = jnp.dtype(cfg.dtype)
    blocks = jax.vmap(lambda k: _block(k, cfg))(
        jax.random.split(k_blocks, cfg.n_layers))
    return {
        "embed_group": {
            "embed": _normal(ke, (v, d), d ** -0.5, dt),
            "lm_head": _normal(kh, (d, v), d ** -0.5, dt),
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        },
        "blocks": [blocks],
    }


# -- reference forward -----------------------------------------------------

def rotary(x, theta):
    """Rotate-half rotary embedding over the whole head; x: (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, c, precision):
    """Causal GQA attention in blocks of ``ATTN_BLOCK`` queries, so a
    4096-token step fits one chip beside nothing else."""
    b, s, _ = x.shape
    hq, hkv, dh = c["heads"], c["kv_heads"], c["head_dim"]
    g = hq // hkv
    h = rms(x, p["norm"]["scale"], c["eps"])
    q = rotary(mm("bsd,dhk->bshk", h, p["wq"], precision), c["theta"])
    k = rotary(mm("bsd,dhk->bshk", h, p["wk"], precision), c["theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"], precision)
    blk = min(ATTN_BLOCK, s)
    nb = s // blk
    qb = q.reshape(b, nb, blk, hkv, g, dh).transpose(1, 0, 3, 4, 2, 5)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, i = args                                  # (B, Hkv, G, blk, D)
        sc = mm("bhgqd,bkhd->bhgqk", qi, k, precision) * dh ** -0.5
        qpos = i * blk + jnp.arange(blk)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return mm("bhgqk,bkhd->bhgqd", w, v, precision)

    o = jax.lax.map(one, (qb, jnp.arange(nb)))       # (nb, B, Hkv, G, blk, D)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, hq, dh)
    return x + mm("bshk,hkd->bsd", o, p["wo"], precision)


def mlp(p, x, c, precision):
    h = rms(x, p["norm"]["scale"], c["eps"])
    gate = mm("bsd,df->bsf", h, p["w_gate"], precision)
    up = mm("bsd,df->bsf", h, p["w_up"], precision)
    return x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"],
                  precision)


def hidden(params, tokens, c, precision):
    """Final hidden states (before the final norm), (B, S, d) f32."""
    x = params["embed_group"]["embed"].astype(F32)[tokens]
    layers = params["blocks"][0]
    for i in range(c["layers"]):
        lp = jax.tree.map(lambda a: a[i].astype(F32), layers)
        x = jax.checkpoint(
            lambda x, lp: mlp(lp["ffn"], attention(lp["attn"], x, c,
                                                   precision), c, precision)
        )(x, lp)
    return x


def constants(cfg):
    """The sizes the reference reads, as a hashable tuple of pairs."""
    return tuple(sorted({
        "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.d_head, "eps": cfg.norm_eps,
        "theta": cfg.rope_theta, "layers": cfg.n_layers}.items()))


# -- model FLOPs -----------------------------------------------------------
# The usual training count: 6 per matmul parameter per trained token
# (forward, and two for the backward), plus causal attention's score and
# value products, ``6 * S^2 * heads * head_dim`` per sequence and layer in
# training; recomputation does not count.  The embedding gather is not a
# matmul.

def matmul_params(cfg) -> int:
    """Matmul parameters one token passes through: layers plus the head."""
    d, dh = cfg.d_model, cfg.d_head
    attn = d * cfg.n_heads * dh * 2 + d * cfg.n_kv_heads * dh * 2
    mlp = 3 * d * cfg.d_ff
    return cfg.n_layers * (attn + mlp) + d * cfg.vocab


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    tokens = batch * seq
    attn = 6 * seq * seq * cfg.n_heads * cfg.d_head * cfg.n_layers * batch
    return 6.0 * matmul_params(cfg) * tokens + attn

"""Granite-4.0-H's hybrid decoder (``granitemoehybrid`` without experts) as
the program runs it.

Each layer is a pre-norm mixer and a pre-norm SwiGLU MLP
(``shared_intermediate_size``), both branches scaled by
``residual_multiplier`` before their residual add.  The mixer is Mamba-2
(``layer_types`` ``mamba``) or GQA attention with no positional encoding
(``attention``, ``position_embedding_type`` ``nope``), its scores scaled by
``attention_multiplier``; the embedding output is scaled by
``embedding_multiplier``.  Mamba-2: in_proj to z, xBC and dt; a causal
depthwise conv over xBC (width ``mamba_d_conv``, with bias) and SiLU; x, B
and C of one group; dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD
y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s + D x_t with
cum_t = sum_{r<=t} A dt_r; y = RMSNorm(y * silu(z)) * w over all of
d_inner; out_proj.

The reference below computes the conv as shifted sums and the SSD in its
masked quadratic form, in blocks of keys against every query, each block
checkpointed: independent of the program's chunked scan and its state
passing.  The program has no tied head and no logit scale,
so a file may give them only untied and at 1; it reads no experts and no
biases.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..reference import F32, mm, rms

# HF config.json key -> ModelConfig field, for the keys the model reads
_MODEL_KEYS = {
    "hidden_size": "d_model",
    "shared_intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "mamba_d_state": "ssm_state",
    "mamba_d_head": "ssm_head_dim",
    "mamba_expand": "ssm_expand",
    "mamba_d_conv": "ssm_conv",
    "mamba_chunk_size": "chunk",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
}
_ACTS = {"silu": "swiglu"}
_KINDS = {"mamba": "mamba2", "attention": "attn"}
_POSITIONS = {"nope": False, "rope": True}

# intermediate_size is the experts' width, read by nothing without experts;
# max_position_embeddings bounds the positions a sequence may take;
# rope_theta changes nothing under NoPE
KEYS = (*_MODEL_KEYS, "num_hidden_layers", "layer_types",
        "position_embedding_type", "hidden_act", "torch_dtype",
        "intermediate_size", "max_position_embeddings")
FIXED = {
    "num_local_experts": 0,
    "num_experts_per_tok": 0,
    "mamba_n_groups": 1,
    "mamba_n_heads": lambda cfg: cfg.ssm_expand * cfg.d_model
    // cfg.ssm_head_dim,
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "attention_bias": False,
    "normalization_function": "rmsnorm",
    "rope_scaling": None,
    "tie_word_embeddings": False,
    "logits_scaling": 1.0,
}
ATTN_BLOCK = 128         # queries per block of the attention scores
SSD_BLOCK = 64          # queries per block of the SSD's quadratic form


def _period(kinds):
    """The shortest pattern whose repeats make up ``kinds``."""
    n = len(kinds)
    return next(tuple(kinds[:p]) for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file: the first
    ``num_hidden_layers`` of ``layer_types``, as repeats of their period."""
    from repro.models import ModelConfig

    kw = {field: conf[key] for key, field in _MODEL_KEYS.items()
          if key in conf}
    n = conf["num_hidden_layers"]
    kinds = [_KINDS[k] for k in conf["layer_types"][:n]]
    if len(kinds) != n:
        raise ValueError(f"{name}: layer_types lists {len(kinds)} layers, "
                         f"num_hidden_layers is {n}")
    kw.update(n_layers=n, block_pattern=_period(kinds), ssm_ffn=True,
              rope=_POSITIONS[conf["position_embedding_type"]],
              act=_ACTS[conf["hidden_act"]], dtype=conf["torch_dtype"])
    kw.update(conf.get("execution", {}))
    return ModelConfig(name=name, **kw)


# -- weights ---------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _attention(key, cfg, dt):
    d, dh = cfg.d_model, cfg.d_head
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    return {"norm": _ones(d),
            "wq": _normal(kq, (d, cfg.n_heads, dh), s, dt),
            "wk": _normal(kk, (d, cfg.n_kv_heads, dh), s, dt),
            "wv": _normal(kv, (d, cfg.n_kv_heads, dh), s, dt),
            "wo": _normal(ko, (cfg.n_heads, dh, d), s * 0.5, dt)}


def _mamba(key, cfg, dt):
    """Mamba-2's initialisation: A ~ U[1, 16], dt ~ exp U[log 1e-3,
    log 1e-1] stored as softplus^-1(dt), conv taps and bias U(+-W^-1/2),
    all float32."""
    d, n, w = cfg.d_model, cfg.ssm_state, cfg.ssm_conv
    d_inner = cfg.ssm_expand * d
    nh = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * n
    k_in, k_out, k_conv, k_decay = jax.random.split(key, 4)
    kw, kb = jax.random.split(k_conv)
    ka, kt = jax.random.split(k_decay)
    bound = w ** -0.5
    step = jnp.exp(jax.random.uniform(kt, (nh,), minval=math.log(1e-3),
                                      maxval=math.log(1e-1)))
    return {"norm": _ones(d),
            "in_proj": _normal(k_in, (d, d_inner + conv_dim + nh),
                               d ** -0.5, dt),
            "conv_w": jax.random.uniform(kw, (w, conv_dim), minval=-bound,
                                         maxval=bound),
            "conv_b": jax.random.uniform(kb, (conv_dim,), minval=-bound,
                                         maxval=bound),
            "a_log": jnp.log(jax.random.uniform(ka, (nh,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "d_skip": jnp.ones((nh,), jnp.float32),
            "gate_norm": _ones(d_inner),
            "out_proj": _normal(k_out, (d_inner, d), d_inner ** -0.5, dt)}


def _mlp(key, cfg, dt):
    d, ff = cfg.d_model, cfg.d_ff
    kg, ku, kd = jax.random.split(key, 3)
    return {"norm": _ones(d),
            "w_up": _normal(ku, (d, ff), d ** -0.5, dt),
            "w_down": _normal(kd, (ff, d), ff ** -0.5, dt),
            "w_gate": _normal(kg, (d, ff), d ** -0.5, dt)}


def _layer(key, cfg, kind):
    ka, _, kf = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    mixer = (("attn", _attention(ka, cfg, dt)) if kind == "attn"
             else ("mamba", _mamba(ka, cfg, dt)))
    return dict([mixer, ("ffn", _mlp(kf, cfg, dt))])


def make_params(key, cfg):
    """Weights of the hybrid exactly as the program's initialiser lays them
    out: one stack (over the pattern's repeats) per pattern position, the
    same key splits, scales and dtypes."""
    keys = jax.random.split(key, len(cfg.block_pattern) + 2)
    ke, kh = jax.random.split(keys[0])
    d, v = cfg.d_model, cfg.vocab
    dt = jnp.dtype(cfg.dtype)
    return {
        "embed_group": {
            "embed": _normal(ke, (v, d), d ** -0.5, dt),
            "lm_head": _normal(kh, (d, v), d ** -0.5, dt),
            "final_norm": _ones(d),
        },
        "blocks": [jax.vmap(lambda k, kind=kind: _layer(k, cfg, kind))(
            jax.random.split(kj, cfg.repeats))
            for kind, kj in zip(cfg.block_pattern, keys[1:])],
    }


# -- reference forward -----------------------------------------------------

def attention(p, x, c, precision):
    """Causal GQA attention with no positional encoding, scores scaled by
    ``attention_multiplier``, in blocks of ``ATTN_BLOCK`` queries."""
    b, s, _ = x.shape
    hq, hkv, dh = c["heads"], c["kv_heads"], c["head_dim"]
    g = hq // hkv
    h = rms(x, p["norm"]["scale"], c["eps"])
    q = mm("bsd,dhk->bshk", h, p["wq"], precision)
    k = mm("bsd,dhk->bshk", h, p["wk"], precision)
    v = mm("bsd,dhk->bshk", h, p["wv"], precision)
    blk = min(ATTN_BLOCK, s)
    nb = s // blk
    qb = q.reshape(b, nb, blk, hkv, g, dh).transpose(1, 0, 3, 4, 2, 5)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, i = args                                  # (B, Hkv, G, blk, D)
        sc = mm("bhgqd,bkhd->bhgqk", qi, k, precision) * c["attn_mult"]
        qpos = i * blk + jnp.arange(blk)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return mm("bhgqk,bkhd->bhgqd", w, v, precision)

    o = jax.lax.map(one, (qb, jnp.arange(nb)))       # (nb, B, Hkv, G, blk, D)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, hq, dh)
    return mm("bshk,hkd->bsd", o, p["wo"], precision)


def ssd(cmat, bmat, x, dt, cum, y0, precision):
    """y_t = y0_t + sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s, in
    blocks of ``SSD_BLOCK`` keys against the whole sequence of queries, the
    exponent masked to the causal past before exp, each block's term
    checkpointed.  cmat, bmat: (B, S, N); x, y0: (B, S, H, P); dt, cum:
    (B, S, H).

    The blocks run over keys, summed into y: so the gradient of x comes
    out block by block.  Over query blocks, every block reads all of x, and
    its gradient would sum in a buffer of x's size per layer, which XLA
    allocates for all layers at once (1.2 GB at 8192 tokens)."""
    b, s, n = cmat.shape
    blk = min(SSD_BLOCK, s)
    nb = s // blk

    def blocks(a):
        return a.reshape(b, nb, blk, *a.shape[2:]).swapaxes(0, 1)
    qpos = jnp.arange(s)

    @jax.checkpoint
    def term(bj, uj, cumj, j):          # (B, blk, N), (B, blk, H, P), (B, blk, H)
        scores = mm("bqn,bkn->bqk", cmat, bj, precision)       # (B, S, blk)
        kpos = j * blk + jnp.arange(blk)
        causal = (qpos[:, None] >= kpos[None, :])[None, :, :, None]
        decay = jnp.exp(jnp.where(
            causal, cum[:, :, None, :] - cumj[:, None, :, :], -jnp.inf))
        return mm("bqkh,bkhp->bqhp", scores[..., None] * decay, uj, precision)

    y, _ = jax.lax.scan(lambda y, a: (y + term(*a), None), y0, (
        blocks(bmat), blocks(x * dt[..., None]), blocks(cum), jnp.arange(nb)))
    return y


def mamba(p, x, c, precision):
    """The Mamba-2 mixer's branch (before the residual multiplier)."""
    b, s, _ = x.shape
    d_inner, n, nh, w = c["d_inner"], c["d_state"], c["ssm_heads"], c["conv"]
    h = rms(x, p["norm"]["scale"], c["eps"])
    proj = mm("bsd,de->bse", h, p["in_proj"], precision)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * n]
    dt = jax.nn.softplus(proj[..., 2 * d_inner + 2 * n:] + p["dt_bias"])
    # causal depthwise conv: shifted sums over the left-padded sequence
    xp = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        xp[:, i:i + s] * p["conv_w"][i] for i in range(w)))
    xs = xbc[..., :d_inner].reshape(b, s, nh, d_inner // nh)
    bmat, cmat = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    cum = jnp.cumsum(-jnp.exp(p["a_log"]) * dt, axis=1)
    y = ssd(cmat, bmat, xs, dt, cum, p["d_skip"][:, None] * xs, precision)
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    y = rms(y, p["gate_norm"]["scale"], c["eps"])
    return mm("bse,ed->bsd", y, p["out_proj"], precision)


def mlp(p, x, c, precision):
    """The SwiGLU MLP's branch (before the residual multiplier)."""
    h = rms(x, p["norm"]["scale"], c["eps"])
    gate = mm("bsd,df->bsf", h, p["w_gate"], precision)
    up = mm("bsd,df->bsf", h, p["w_up"], precision)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"], precision)


def _layer_forward(kind, lp, x, c, precision):
    """One layer, its mixer and MLP branches each checkpointed: a layer's
    backward holds one branch's intermediates at a time."""
    mixer = attention if kind == "attn" else mamba
    x = jax.checkpoint(lambda x, p: x + c["res_mult"] * mixer(
        p, x, c, precision))(x, lp["attn" if kind == "attn" else "mamba"])
    return jax.checkpoint(lambda x, p: x + c["res_mult"] * mlp(
        p, x, c, precision))(x, lp["ffn"])


def hidden(params, tokens, c, precision):
    """Final hidden states (before the final norm), (B, S, d) f32; each
    layer checkpointed."""
    x = params["embed_group"]["embed"].astype(F32)[tokens] * c["emb_mult"]
    for r in range(c["repeats"]):
        for kind, stack in zip(c["pattern"], params["blocks"]):
            lp = jax.tree.map(lambda a: a[r].astype(F32), stack)
            x = jax.checkpoint(
                lambda x, lp, kind=kind: _layer_forward(kind, lp, x, c,
                                                        precision))(x, lp)
    return x


def constants(cfg):
    """The sizes the reference reads, as a hashable tuple of pairs."""
    d_inner = cfg.ssm_expand * cfg.d_model
    return tuple(sorted({
        "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.d_head, "eps": cfg.norm_eps,
        "pattern": cfg.block_pattern, "repeats": cfg.repeats,
        "d_inner": d_inner, "d_state": cfg.ssm_state,
        "ssm_heads": d_inner // cfg.ssm_head_dim, "conv": cfg.ssm_conv,
        "emb_mult": cfg.embedding_multiplier,
        "res_mult": cfg.residual_multiplier,
        "attn_mult": cfg.attention_multiplier or cfg.d_head ** -0.5,
    }.items()))


# -- model FLOPs -----------------------------------------------------------
# The usual training count, 6 per matmul parameter per trained token
# (forward, and two for the backward), plus:
# * causal attention's score and value products in the attention layers,
#   ``6 * S^2 * heads * head_dim`` per sequence and layer;
# * the SSD in its chunked form (chunk c, H heads of P, state N, one B/C
#   group) in the Mamba-2 layers: per chunk, C B^T once (causal: c^2 N)
#   and its decay-weighted product with x (causal: c^2 H P), the carried
#   state read by C (2 c H N P) and the chunk's B^T x written into it
#   (2 c H N P); per sequence and layer, forward
#   ``S (c N + c H P + 4 H N P)``, and three times that in training.
# Recomputation does not count; the conv, gates and norms are not matmuls,
# nor is the embedding gather.

def matmul_params(cfg) -> int:
    """Matmul parameters one token passes through: layers plus the head."""
    d, dh = cfg.d_model, cfg.d_head
    d_inner = cfg.ssm_expand * d
    nh = d_inner // cfg.ssm_head_dim
    per = {"attn": d * dh * (2 * cfg.n_heads + 2 * cfg.n_kv_heads),
           "mamba2": d * (2 * d_inner + 2 * cfg.ssm_state + nh)
           + d_inner * d}
    mlp = 3 * d * cfg.d_ff
    layers = sum(per[k] + mlp for k in cfg.block_pattern) * cfg.repeats
    return layers + d * cfg.vocab


def ssd_flops(cfg, seq: int) -> float:
    """Forward FLOPs of one sequence through one Mamba-2 layer's SSD."""
    c = min(cfg.chunk, seq)
    h = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    p, n = cfg.ssm_head_dim, cfg.ssm_state
    return seq * (c * n + c * h * p + 4 * h * n * p)


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    tokens = batch * seq
    n_attn = cfg.block_pattern.count("attn") * cfg.repeats
    n_mamba = cfg.block_pattern.count("mamba2") * cfg.repeats
    attn = 6 * seq * seq * cfg.n_heads * cfg.d_head * n_attn * batch
    return (6.0 * matmul_params(cfg) * tokens + attn
            + 3.0 * ssd_flops(cfg, seq) * n_mamba * batch)

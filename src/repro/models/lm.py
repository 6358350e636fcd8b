"""Generic decoder-only LM assembled from a block pattern.

One implementation serves all ten assigned architectures: the config's
``block_pattern`` (cycled ``repeats`` times to n_layers) names the mixer
of each layer; FFNs are dense or MoE; weights of ``shared_attn`` blocks
are shared across repeats (Zamba-style).

Layer stacking: parameters of each pattern position are *stacked* over
repeats and the forward pass is a single ``lax.scan`` over repeats —
the compiled HLO contains each distinct layer body once, keeping 94-100
layer configs compilable in seconds and enabling per-repeat activation
rematerialisation (``cfg.remat``).

Three entry points (pure functions of params):
  forward(params, cfg, batch)             -> final hidden states (B,S,d)
  loss(params, cfg, batch)                -> scalar LM loss
  prefill(params, cfg, batch, cache)      -> (hidden, cache)
  decode_step(params, cfg, tok, cache)    -> (logits, cache)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.dist.sharding import logical
from repro.kernels.flash_attention.kernel import default_blocks
from . import ssm
from .config import ModelConfig
from .layers import (
    attention,
    chunked_cross_entropy,
    embed_tokens,
    init_attention,
    init_attention_cache,
    init_embed,
    init_mlp,
    lm_logits,
    mlp,
)
from .moe import init_moe, moe_ffn

ATTN_KINDS = ("attn", "cross_attn", "shared_attn")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    ka, kc, kf = jax.random.split(key, 3)
    p: Dict[str, Any] = {}
    if kind in ("attn", "shared_attn"):
        p["attn"] = init_attention(ka, cfg)
    elif kind == "cross_attn":
        p["attn"] = init_attention(ka, cfg)
        p["xattn"] = init_attention(kc, cfg)
    elif kind == "mamba2":
        p["mamba"] = init_mamba2_wrap(ka, cfg)
    elif kind == "mlstm":
        p["mlstm"] = ssm.init_mlstm(ka, cfg)
    elif kind == "slstm":
        p["slstm"] = ssm.init_slstm(ka, cfg)
    else:
        raise ValueError(kind)
    # FFN: attention-style blocks carry the MLP/MoE; pure mixers don't,
    # except mamba2 blocks under ``cfg.ssm_ffn`` (Granite-4.0-H puts an
    # MLP in every layer; Zamba only in its shared block).
    with_ffn = kind in ATTN_KINDS or (kind == "mamba2" and cfg.ssm_ffn)
    if with_ffn and (cfg.is_moe or cfg.d_ff > 0):
        p["ffn"] = init_moe(kf, cfg) if cfg.is_moe else init_mlp(kf, cfg)
    return p


def init_mamba2_wrap(key, cfg):
    return ssm.init_mamba2(key, cfg)


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.block_pattern) + 2)
    params: Dict[str, Any] = {"embed_group": init_embed(keys[0], cfg)}
    blocks = []
    shared = None
    for j, kind in enumerate(cfg.block_pattern):
        kj = keys[j + 1]
        if kind == "shared_attn":
            # single copy, shared across repeats
            if shared is None:
                shared = _init_block(kj, cfg, kind)
            blocks.append(None)
        else:
            stacked = jax.vmap(
                lambda k: _init_block(k, cfg, kind)
            )(jax.random.split(kj, cfg.repeats))
            blocks.append(stacked)
    params["blocks"] = blocks
    if shared is not None:
        params["shared"] = shared
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _apply_mixer(
    kind: str,
    p,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    image_mem: Optional[jax.Array],
    cache_entry,
    decode: bool,
):
    """The block's sequence mixer.  Returns (x, new_cache_entry)."""
    new_cache = cache_entry
    if kind in ("attn", "shared_attn", "cross_attn"):
        att_cache = None if cache_entry is None else cache_entry["attn"]
        x, c = attention(p["attn"], cfg, x, positions, cache=att_cache)
        if kind == "cross_attn":
            x, _ = attention(p["xattn"], cfg, x, positions, kv=image_mem,
                             causal=False)
        if cache_entry is not None:
            new_cache = dict(cache_entry)
            new_cache["attn"] = c if c is not None else cache_entry["attn"]
    elif kind == "mamba2":
        st = None if cache_entry is None else cache_entry["state"]
        if decode:
            x, st = ssm.mamba2_decode(p["mamba"], cfg, x, st)
        else:
            x, st = ssm.mamba2(p["mamba"], cfg, x, st)
        if cache_entry is not None:
            new_cache = {"state": st}
    elif kind == "mlstm":
        st = None if cache_entry is None else cache_entry["state"]
        if decode:
            x, st = ssm.mlstm_decode(p["mlstm"], cfg, x, st)
        else:
            x, st = ssm.mlstm(p["mlstm"], cfg, x, st)
        if cache_entry is not None:
            new_cache = {"state": st}
    elif kind == "slstm":
        st = None if cache_entry is None else cache_entry["state"]
        x, st = ssm.slstm(p["slstm"], cfg, x, st)
        if cache_entry is not None:
            new_cache = {"state": st}
    else:
        raise ValueError(kind)
    return x, new_cache


def _apply_ffn(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The block's FFN, where it has one."""
    if "ffn" in (p or {}):
        x = moe_ffn(p["ffn"], cfg, x) if cfg.is_moe else mlp(p["ffn"], cfg, x)
    return x


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, batch):
    if cfg.frontend == "embed_stub":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params["embed_group"], batch["tokens"])
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    x = _embed(params, cfg, batch)
    image_mem = batch.get("image_embeds")
    if image_mem is not None:
        image_mem = image_mem.astype(x.dtype)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, image_mem, positions


def _remat_in_order(apply):
    """``apply(p, x, aux) -> x`` rematerialised: its backward recomputes the
    forward from (p, x) once the output's cotangent has arrived; ``aux``
    (positions, image memory) is not differentiated.  Under
    ``jax.checkpoint`` nothing orders a block's recomputation after the
    backward of the blocks above it, and XLA schedules the recomputations
    of a whole pattern early, holding every block's intermediates at once;
    the optimization barrier ties each to its cotangent (and keeps it from
    being merged with the forward pass)."""
    @jax.custom_vjp
    def f(p, x, aux):
        return apply(p, x, aux)

    def fwd(p, x, aux):
        return apply(p, x, aux), (p, x, aux)

    def bwd(res, g):
        (p, x, aux), g = jax.lax.optimization_barrier((res, g))
        _, vjp = jax.vjp(lambda p, x: apply(p, x, aux), p, x)
        return (*vjp(g), jax.tree.map(lambda a: None, aux))

    f.defvjp(fwd, bwd)
    return f


def _scan_blocks(params, cfg: ModelConfig, x, positions, image_mem,
                 cache, decode: bool):
    """lax.scan over repeats; python loop over pattern positions inside.

    With ``cfg.remat`` the backward pass holds one unit's intermediates at
    a time.  A one-block pattern rematerialises each block, ordered by the
    scan; a longer one each block's mixer and FFN apart, ordered by
    ``_remat_in_order``: a pattern's layers sit in one scan step, and at
    long sequences a whole block's intermediates are several GB."""
    shared = params.get("shared")
    ordered = len(cfg.block_pattern) > 1 and cache is None
    aux = (positions, image_mem)

    def block(kind):
        def mixer(pj, xx, cj, aux):
            if cfg.seq_shard and not decode:
                xx = logical(xx, "batch", "seq", None)
            return _apply_mixer(kind, pj, cfg, xx, *aux, cj, decode)

        def apply(pj, xx, cj):
            xx, cj = mixer(pj, xx, cj, aux)
            return _apply_ffn(pj, cfg, xx), cj
        if not cfg.remat or decode:
            return apply
        if not ordered:
            return jax.checkpoint(apply)
        mix = _remat_in_order(lambda pm, xx, a: mixer(pm, xx, None, a)[0])
        ffn = _remat_in_order(lambda pf, xx, a: _apply_ffn(pf, cfg, xx))

        def units(pj, xx, cj):
            pm = {k: v for k, v in pj.items() if k != "ffn"}
            pf = {k: v for k, v in pj.items() if k == "ffn"}
            return ffn(pf, mix(pm, xx, aux), ()), None
        return units

    def body(xc, xs):
        xx, _ = xc
        rep_params, rep_cache = xs
        new_rep_cache = []
        for j, kind in enumerate(cfg.block_pattern):
            pj = shared if kind == "shared_attn" else rep_params[j]
            cj = None if rep_cache is None else rep_cache[j]
            xx, cj_new = block(kind)(pj, xx, cj)
            new_rep_cache.append(cj_new)
        if rep_cache is None:
            return (xx, None), None
        return (xx, None), new_rep_cache

    # xs pytrees: blocks list with leading dim = repeats (None for shared)
    xs_params = [
        b if b is not None else None for b in params["blocks"]
    ]
    # replace None entries (shared) with dummy zeros so scan shapes match
    xs_params = [b if b is not None else jnp.zeros((cfg.repeats,))
                 for b in xs_params]

    if cfg.scan_layers:
        (x, _), new_cache = jax.lax.scan(
            body, (x, None), (xs_params, cache))
    else:
        new_cache_list = []
        for r in range(cfg.repeats):
            rep_params = jax.tree.map(lambda a: a[r], xs_params)
            rep_cache = (None if cache is None
                         else jax.tree.map(lambda a: a[r], cache))
            (x, _), nc = body((x, None), (rep_params, rep_cache))
            new_cache_list.append(nc)
        new_cache = (None if cache is None else jax.tree.map(
            lambda *xs: jnp.stack(xs), *new_cache_list))
    return x, new_cache


def forward(params, cfg: ModelConfig, batch) -> jax.Array:
    """Final hidden states.  Self-attention runs through the causal flash
    kernel where ``forward_attention`` says so (training differentiates it
    through the kernel's backward), else ``cfg.attn_impl``."""
    if forward_attention(params, cfg, batch) == "flash":
        cfg = cfg.with_(attn_impl="pallas")
    x, image_mem, positions = _inputs(params, cfg, batch)
    x, _ = _scan_blocks(params, cfg, x, positions, image_mem, None, False)
    return x


def loss(params, cfg: ModelConfig, batch) -> jax.Array:
    h = forward(params, cfg, batch)
    return chunked_cross_entropy(
        params["embed_group"], cfg, h, batch["targets"],
        weights=batch.get("loss_weights"))


def logits(params, cfg: ModelConfig, batch) -> jax.Array:
    h = forward(params, cfg, batch)
    return lm_logits(params["embed_group"], cfg, h)


# ---------------------------------------------------------------------------
# LGD feature-extraction hooks (paper Sec. 3.2: the BERT recipe)
# ---------------------------------------------------------------------------

def attention_path(params, seq: int) -> str:
    """The self-attention path of ``forward`` (the loss and its gradient,
    the LGD refresh embed) for ``params`` and rows of ``seq`` tokens:
    ``"flash"``, the causal Pallas kernel and its backward, where the
    backend is TPU, the params live on one device (Mosaic kernels do not
    run in a mesh-partitioned program) and the kernel's blocks divide
    ``seq``; else ``"chunked"``, the XLA scan.  Reads only avals, so it
    holds at trace time too."""
    if (kernels.default_use_pallas() and default_blocks(seq) is not None
            and all(jax.typeof(x).sharding.mesh.size <= 1
                    for x in jax.tree.leaves(params))):
        return "flash"
    return "chunked"


def forward_attention(params, cfg: ModelConfig, batch) -> str:
    """``attention_path`` of ``forward(params, cfg, batch)``."""
    rows = batch["embeds"] if cfg.frontend == "embed_stub" else batch["tokens"]
    return attention_path(params, rows.shape[1])


def pooled_features(params, cfg: ModelConfig, batch) -> jax.Array:
    """Per-example feature vector: mean-pooled final hidden state (f32).

    The paper hashes each example's pooled last-layer representation into
    the LSH index; this is the model-side half of that contract (the
    pipeline half is ``repro.data.LSHSampledPipeline``).  Nothing
    differentiates it, so where its self-attention takes the flash kernel
    (``attention_path``) it runs the kernel's forward alone.
    """
    h = forward(params, cfg, batch)
    return jnp.mean(h.astype(jnp.float32), axis=1)


def lm_head_query(params) -> jax.Array:
    """LGD query from the output layer (paper: classification-layer
    weights as queries): the mean lm_head column, in feature space."""
    w = params["embed_group"]["lm_head"].astype(jnp.float32)
    return jnp.mean(w, axis=1)


# ---------------------------------------------------------------------------
# cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked (repeats, ...) cache per pattern position."""
    def one(kind):
        if kind in ATTN_KINDS:
            return {"attn": init_attention_cache(cfg, batch, max_len)}
        if kind == "mamba2":
            return {"state": ssm.init_mamba2_state(cfg, batch)}
        if kind == "mlstm":
            return {"state": ssm.init_mlstm_state(cfg, batch)}
        if kind == "slstm":
            return {"state": ssm.init_slstm_state(cfg, batch)}
        raise ValueError(kind)

    return [
        jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.repeats,) + a.shape).copy(),
            one(kind),
        )
        for kind in cfg.block_pattern
    ]


def prefill(params, cfg: ModelConfig, batch, cache):
    """Run the prompt through the model, filling caches; returns (h, cache).

    Attention caches are written as full-sequence K/V (the train path);
    SSM states come out of the chunked scan.
    """
    x, image_mem, positions = _inputs(params, cfg, batch)
    x, new_cache = _scan_blocks(
        params, cfg, x, positions, image_mem, cache, False)
    return x, new_cache


def decode_hidden(params, cfg: ModelConfig, batch, cache):
    """One-token decode up to (but not including) the lm head.

    The transformer body of ``decode_step``, split out so alternative
    heads (e.g. the LSH-shortlisted head in ``models.sampled_softmax``)
    can reuse the unchanged block stack without paying the O(V) logits
    matmul.  Returns (hidden (B, 1, d), new_cache)."""
    x = _embed(params, cfg, batch)
    image_mem = batch.get("image_embeds")
    if image_mem is not None:
        image_mem = image_mem.astype(x.dtype)
    positions = batch["positions"]           # (B, 1) int32
    return _scan_blocks(params, cfg, x, positions, image_mem, cache, True)


def decode_step(params, cfg: ModelConfig, batch, cache):
    """One-token decode: batch["tokens"]/batch["embeds"] has S=1.

    Returns (logits (B, 1, V), new_cache)."""
    x, new_cache = decode_hidden(params, cfg, batch, cache)
    return lm_logits(params["embed_group"], cfg, x), new_cache

"""Inputs and weights made from ``--seed``, on the device.

The corpus follows the program's synthetic LM corpus: tokens from a Zipf
law over the vocabulary, with a ``hard_frac`` share of rows drawn from the
same law over a permuted vocabulary.  It is drawn on the device by inverse
CDF, so a 1024 x 4097 shard costs one small program, not a host loop.

``make_params`` draws the model's weights exactly as the program's
initialiser lays them out (same key splits, scales and dtype); the
benchmark's tests pin the two equal, and the reference draws its own copy
from here, never from the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# stream of each consumer of the run's root key
CORPUS, PARAMS, PIPELINE = 1, 2, 3


def root_key(seed: int):
    """A key for any whole seed, including ones past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def stream(seed: int, which: int):
    return jax.random.fold_in(root_key(seed), which)


@functools.partial(jax.jit, static_argnames=("rows", "seq", "vocab",
                                             "zipf", "hard_frac"))
def make_corpus(key, *, rows, seq, vocab, zipf, hard_frac):
    """(rows, seq + 1) int32 tokens and the (rows,) hard-row mask."""
    ke, kh, kp, km = jax.random.split(key, 4)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** -zipf
    cdf = jnp.cumsum(probs / jnp.sum(probs))

    def draw(k):
        u = jax.random.uniform(k, (rows, seq + 1)) * cdf[-1]
        return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                           vocab - 1).astype(jnp.int32)

    perm = jax.random.permutation(kp, vocab).astype(jnp.int32)
    hard = jax.random.uniform(km, (rows,)) < hard_frac
    tokens = jnp.where(hard[:, None], perm[draw(kh)], draw(ke))
    return tokens, hard


def corpus_for(seed: int, cfg, traffic: dict):
    return make_corpus(
        stream(seed, CORPUS), rows=traffic["corpus_rows"],
        seq=traffic["seq"], vocab=cfg.vocab,
        zipf=float(traffic["zipf_exponent"]),
        hard_frac=float(traffic["hard_frac"]))


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
    in the layout and dtype the program trains (layers stacked on axis 0)."""
    if cfg.block_pattern != ("attn",) or cfg.act != "swiglu" or cfg.is_moe:
        raise ValueError(f"{cfg.name}: only dense SwiGLU attention stacks")
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


def params_on(key, cfg, shardings):
    """``make_params`` as one jitted call straight into ``shardings``."""
    return jax.jit(make_params, static_argnums=1,
                   out_shardings=shardings)(key, cfg)

"""Jit'd public wrappers: dispatch between the Pallas kernel and the oracle.

The model code calls these with ``use_pallas`` from
``ModelConfig.attn_impl == "pallas"`` (no shipped config sets it, so
training uses the chunked XLA attention); CPU smoke tests run the oracle
(XLA:CPU) and the kernel tests run interpret mode.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas, flash_decode_pallas
from .ref import attention_ref, decode_ref


@partial(jax.jit, static_argnames=("causal", "use_pallas", "interpret",
                                   "block_q", "block_k"))
def gqa_attention(
    q: jax.Array,   # (B, S, Hq, D)  — model layout
    k: jax.Array,   # (B, S, Hkv, D)
    v: jax.Array,   # (B, S, Hkv, D)
    *,
    causal: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
    block_q: int = 256,
    block_k: int = 256,
) -> jax.Array:
    """Grouped-query attention; returns (B, S, Hq, D)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,S,D)
    kg = k.transpose(0, 2, 1, 3)                              # (B,Hkv,S,D)
    vg = v.transpose(0, 2, 1, 3)
    if use_pallas:
        out = flash_attention_pallas(
            qg, kg, vg, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    else:
        out = attention_ref(qg, kg, vg, causal=causal)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_k"))
def gqa_decode(
    q: jax.Array,        # (B, 1, Hq, D)
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, D)
    kv_len: jax.Array,   # (B,)
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    block_k: int = 512,
) -> jax.Array:
    """Single-token decode against a KV cache; returns (B, 1, Hq, D)."""
    b, one, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = q[:, 0].reshape(b, hkv, g, d)
    kg = k_cache.transpose(0, 2, 1, 3)
    vg = v_cache.transpose(0, 2, 1, 3)
    if use_pallas:
        out = flash_decode_pallas(
            qg, kg, vg, kv_len, block_k=block_k, interpret=interpret
        )
    else:
        out = decode_ref(qg, kg, vg, kv_len)
    return out.reshape(b, 1, hq, d)

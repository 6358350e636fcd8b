"""Memory-efficient chunked attention in pure XLA (lax.scan over q-blocks).

This is the XLA twin of the Pallas flash kernel: on the dry-run host
(and any non-TPU backend) it gives the same O(S * block) activation
memory so 32k-token prefill/train cells fit HBM, while keeping the HLO
analyzable for the roofline accounting.  On one TPU device the kernel and
its backward replace it in training and in the refresh embed
(``repro.models.lm.attention_path``); this scan runs on the CPU, in
mesh-partitioned programs and where no kernel block divides the
sequence.

Schedule: outer lax.scan over query blocks; each step attends its block
to the full (masked) KV — softmax in f32 with the usual max-subtraction.
The step body is rematerialised so the backward pass recomputes the
(block_q x S) score matrix instead of storing it.

Note the causal mask is applied by `where`, so the XLA path spends ~2x
the minimal causal FLOPs on above-diagonal blocks; the Pallas kernel
skips those blocks structurally.  Recorded in EXPERIMENTS.md §Roofline.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def chunked_gqa_attention(
    q: jax.Array,   # (B, S, Hq, D)
    k: jax.Array,   # (B, S_kv, Hkv, D)
    v: jax.Array,   # (B, S_kv, Hkv, D)
    *,
    causal: bool = True,
    block_q: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    b, s, hq, d = q.shape
    s_kv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    bq = min(block_q, s)
    pad = (-s) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (s + pad) // bq

    qg = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, Hkv, G, bq, D)
    kg = k.transpose(0, 2, 1, 3)         # (B, Hkv, S_kv, D)
    vg = v.transpose(0, 2, 1, 3)
    kv_pos = jnp.arange(s_kv)

    @jax.checkpoint
    def step(carry, xs):
        qi, block_idx = xs               # (B,Hkv,G,bq,D), scalar
        logits = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qi.astype(jnp.float32),
            kg.astype(jnp.float32)) * scale
        if causal:
            q_pos = block_idx * bq + jnp.arange(bq)
            mask = q_pos[:, None] >= kv_pos[None, :]
            logits = jnp.where(mask[None, None, None], logits, -1e30)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p / jnp.maximum(l, 1e-30),
                       vg.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, outs = jax.lax.scan(step, (), (qg, jnp.arange(nq)))
    # (nq, B, Hkv, G, bq, D) -> (B, S, Hq, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(
        b, nq * bq, hq, d)
    return out[:, :s]

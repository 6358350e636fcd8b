"""Causal GQA flash attention, Pallas TPU.

Online-softmax tiling (Flash-Attention 2 schedule adapted to the TPU
memory hierarchy): the KV sequence is the innermost *grid* dimension so
each (batch*head, q-block) owns VMEM scratch carrying the running max
``m``, normaliser ``l`` and accumulator ``acc`` across KV steps; XLA's
Pallas pipeline overlaps the HBM->VMEM streaming of the next KV block
with the MXU matmuls of the current one.

Causality is exploited structurally: KV blocks strictly above the
diagonal contribute nothing, so their compute is skipped with pl.when and
their K/V index map is clamped to the last block the q-block needs, so a
skipped grid step fetches nothing new (the roofline win: 2x fewer MXU
FLOPs and K/V bytes at long sequence).  Only blocks that cross the
diagonal build the causal mask.

GQA: queries arrive grouped as (B, Hkv, G, S, D) so one KV head's block
is shared by its G query heads without re-streaming K/V — the layout
turns grouped attention into a plain batched matmul over the fused
(G*bq, D) tile.  Both matmuls feed the MXU operands in the inputs' dtype
(P is cast to V's dtype) and accumulate in f32; the running max,
normaliser and accumulator stay f32.

Block sizes default to ``default_blocks(S)``: (512, 512) where they divide
S, the largest power-of-two divisor down to 128 otherwise — chosen by a
sweep of {256, 512}^2 on a v5e at Granite-3-8B's embed shape (PERF.md).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def default_blocks(s: int) -> tuple[int, int] | None:
    """(block_q, block_k) for sequence length ``s``, or None where no
    lane-aligned block divides it (``s`` not a multiple of 128)."""
    if s % 128:
        return None
    return math.gcd(s, DEFAULT_BQ), math.gcd(s, DEFAULT_BK)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, bq: int, bk: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        q = q_ref[0]                         # (G*bq, D) fused group-of-queries
        k = k_ref[0]                         # (bk, D)
        v = v_ref[0]                         # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # (G*bq, bk)
        if masked:
            g_bq = q.shape[0]
            q_pos = qi * bq + (
                jax.lax.broadcasted_iota(jnp.int32, (g_bq, bk), 0) % bq
            )
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (g_bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...]                  # (G*bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)               # (G*bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # blocks strictly above the diagonal are skipped; those wholly
        # below it need no mask
        run = ki * bk < (qi + 1) * bq
        below = (ki + 1) * bk <= qi * bq + 1
        pl.when(run & below)(lambda: step(False))
        pl.when(run & jnp.logical_not(below))(lambda: step(True))
    else:
        step(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # (B, Hkv, G, S, D) — G query heads per KV head
    k: jax.Array,   # (B, Hkv, S, D)
    v: jax.Array,   # (B, Hkv, S, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hkv, G, S, D) attention output.  Block sizes left at
    None come from ``default_blocks(S)``."""
    b, hkv, g, s, d = q.shape
    assert k.shape == (b, hkv, s, d) and v.shape == (b, hkv, s, d)
    if block_q is None or block_k is None:
        blocks = default_blocks(s)
        assert blocks is not None, f"no default block divides S={s}"
        block_q = block_q or blocks[0]
        block_k = block_k or blocks[1]
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = scale if scale is not None else d ** -0.5
    nq, nk = s // bq, s // bk
    bh = b * hkv

    # rows grouped as (G, bq) per q-block: reorder to (bh, nq*g*bq, d)
    qf = q.reshape(bh, g, nq, bq, d).transpose(0, 2, 1, 3, 4).reshape(
        bh, nq * g * bq, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)

    if causal:
        # past the q-block's last needed KV block, keep the block index
        # unchanged: the pipeline then fetches nothing for skipped steps
        def kv_map(h, i, j):
            return (h, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0)
    else:
        def kv_map(h, i, j):
            return (h, j, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g * bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, g * bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * g * bq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(bh, nq, g, bq, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, hkv, g, s, d)


# ---------------------------------------------------------------------------
# flash decode: one query token against a long KV cache
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, bk: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                             # (G, D) — all grouped heads
    k = k_ref[0]                             # (bk, D)
    v = v_ref[0]
    kv_len = len_ref[pl.program_id(0)]       # valid cache length (SMEM)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                # (G, bk)
    pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_decode_pallas(
    q: jax.Array,        # (B, Hkv, G, D) single new token
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    kv_len: jax.Array,   # (B,) int32 valid lengths
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hkv, G, D)."""
    b, hkv, g, d = q.shape
    s = k_cache.shape[2]
    bk = min(block_k, s)
    assert s % bk == 0
    scale = scale if scale is not None else d ** -0.5
    bh = b * hkv
    qf = q.reshape(bh, g, d)
    kf = k_cache.reshape(bh, s, d)
    vf = v_cache.reshape(bh, s, d)
    lens = jnp.repeat(kv_len.astype(jnp.int32), hkv)  # (bh,)

    # the per-row lengths are scalar-prefetch operands (SMEM): a (1,)
    # block of a vector is not tile-aligned for Mosaic.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, s // bk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h, j, lens: (h, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, lens: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, lens: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda h, j, lens: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(b, hkv, g, d)

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

Backward (``jax.custom_vjp``, the FlashAttention-2 schedule): the
differentiated forward also writes each query row's log-sum-exp
``m + log l`` (f32, broadcast over one lane tile); a non-differentiated
call runs the forward alone, unchanged.  Two kernels recompute P from q,
k and the saved log-sum-exp, with D = rowsum(dO * O):

* dK/dV on a (bh, kv-block, q-block) grid accumulates one KV block's
  gradients in VMEM over every q-block and, through the fused (G*bq)
  rows, over the KV head's G query heads.  It works on the transposed
  tile (bk, G*bq), so the row statistics arrive as lane-dense rows and
  every matmul is a plain or right-transposed one;
* dQ on a (bh, q-block, kv-block) grid, on the forward's tile.

Both skip the blocks above the diagonal, clamp their index maps as the
forward does so skipped steps fetch nothing, mask only the blocks that
cross it, and feed the MXU in the inputs' dtype (P and dS cast to it)
with f32 accumulation.  On one TPU device training runs this kernel
(``repro.models.lm.attention_path``).

Block sizes default to ``default_blocks(S)``: (512, 512) where they divide
S, the largest power-of-two divisor down to 128 otherwise — chosen by a
sweep of {256, 512}^2 on a v5e at Granite-3-8B's embed shape, and best
for the gradient too at Granite's and Granite-4.0-H's training shapes
(PERF.md).
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
LANES = 128        # the saved log-sum-exp is broadcast over a lane tile


def default_blocks(s: int) -> tuple[int, int] | None:
    """(block_q, block_k) for sequence length ``s``, or None where no
    lane-aligned block divides it (``s`` not a multiple of 128)."""
    if s % 128:
        return None
    return math.gcd(s, DEFAULT_BQ), math.gcd(s, DEFAULT_BK)


def _visible(qi, ki, shape, q_axis: int, bq: int, bk: int):
    """Causal mask of a score tile of ``shape`` between q-block ``qi`` and
    kv-block ``ki``, whose fused (G*bq) query axis is ``q_axis`` and whose
    key axis is the other."""
    q_pos = qi * bq + (
        jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) % bq
    )
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos >= k_pos


def _run_tile(step, qi, ki, bq: int, bk: int, causal: bool):
    """``step(masked)`` on the tile of q-block ``qi`` and kv-block ``ki``:
    under ``causal`` skipped above the diagonal, unmasked wholly below it
    and masked only where it crosses."""
    if causal:
        run = ki * bk < (qi + 1) * bq
        below = (ki + 1) * bk <= qi * bq + 1
        pl.when(run & below)(lambda: step(False))
        pl.when(run & jnp.logical_not(below))(lambda: step(True))
    else:
        step(False)


def _kv_map(bq: int, bk: int, causal: bool):
    """K/V block index of grid step (h, q-block i, kv-block j)."""
    if causal:
        # past the q-block's last needed KV block, keep the block index
        # unchanged: the pipeline then fetches nothing for skipped steps
        def kv_map(h, i, j):
            return (h, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0)
    else:
        def kv_map(h, i, j):
            return (h, j, 0)
    return kv_map


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                  scale: float, bq: int, bk: int, causal: bool,
                  with_lse: bool):
    lse_ref = refs[0] if with_lse else None
    m_scr, l_scr, acc_scr = refs[-3:]
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
            s = jnp.where(_visible(qi, ki, s.shape, 0, bq, bk), s, NEG_INF)
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

    _run_tile(step, qi, ki, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)
        if with_lse:
            lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _forward(qf, kf, vf, g, bq, bk, scale, causal, interpret, with_lse):
    """The forward kernel on the fused layout: (bh, nq*G*bq, D) queries,
    (bh, S, D) keys and values.  Returns the output, and with ``with_lse``
    also each query row's log-sum-exp, f32, broadcast over ``LANES``."""
    bh, rows, d = qf.shape
    nq, nk = kf.shape[1] // bq, kf.shape[1] // bk
    out_spec = pl.BlockSpec((1, g * bq, d), lambda h, i, j: (h, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, rows, d), qf.dtype)
    if with_lse:
        out_spec = [out_spec, pl.BlockSpec((1, g * bq, LANES),
                                           lambda h, i, j: (h, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, rows, LANES), jnp.float32)]
    kv_map = _kv_map(bq, bk, causal)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
            with_lse=with_lse,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g * bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale: float, bq: int, bk: int,
                causal: bool):
    """dK and dV of one KV block, accumulated over the q-blocks (the inner
    grid axis) and, through the fused (G*bq) rows, over the KV head's G
    query heads.  Works on the transposed tile (bk, G*bq), so the row
    statistics arrive as lane-dense rows and every matmul is plain."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        q = q_ref[0]                         # (G*bq, D)
        k = k_ref[0]                         # (bk, D)
        v = v_ref[0]                         # (bk, D)
        do = do_ref[0]                       # (G*bq, D)
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # (bk, G*bq)
        if masked:
            s = jnp.where(_visible(qi, ki, s.shape, 1, bq, bk), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])          # lse row (1, G*bq)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                    # (bk, G*bq)
        ds = p * (dp - di_ref[0])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_tile(step, qi, ki, bq, bk, causal)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, di_scr, *, scale: float, bq: int, bk: int,
               causal: bool):
    """dQ of one fused (G*bq) q-block, accumulated over the KV blocks (the
    inner grid axis), on the forward's tile layout."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        di_scr[...] = jnp.sum(
            o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True)          # rowsum(dO * O), (G*bq, 1)

    def step(masked: bool):
        q = q_ref[0]                         # (G*bq, D)
        k = k_ref[0]                         # (bk, D)
        v = v_ref[0]                         # (bk, D)
        do = do_ref[0]                       # (G*bq, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # (G*bq, bk)
        if masked:
            s = jnp.where(_visible(qi, ki, s.shape, 0, bq, bk), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                    # (G*bq, bk)
        ds = p * (dp - di_scr[...])
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_tile(step, qi, ki, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _backward(qf, kf, vf, o, lse, do, g, bq, bk, scale, causal, interpret):
    """dQ, dK, dV on the fused layout, P recomputed from the saved
    log-sum-exp."""
    bh, rows, d = qf.shape
    nq, nk = kf.shape[1] // bq, kf.shape[1] // bk
    lse_row = lse[:, :, 0].reshape(bh, 1, rows)
    di_row = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                     axis=-1).reshape(bh, 1, rows)    # rowsum(dO * O)

    if causal:
        # before the first q-block that sees kv-block j, keep that block's
        # index: skipped steps fetch nothing
        def q_block(j, i):
            return jnp.maximum(i, (j * bk) // bq)
    else:
        def q_block(j, i):
            return i
    q_spec = pl.BlockSpec((1, g * bq, d),
                          lambda h, j, i: (h, q_block(j, i), 0))
    row_spec = pl.BlockSpec((1, 1, g * bq),
                            lambda h, j, i: (h, 0, q_block(j, i)))
    kv_spec = pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(bh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, do, lse_row, di_row)

    kv_map = _kv_map(bq, bk, causal)
    row_block = pl.BlockSpec((1, g * bq, d), lambda h, i, j: (h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(bh, nq, nk),
        in_specs=[
            row_block,
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            row_block,
            row_block,
            pl.BlockSpec((1, g * bq, LANES), lambda h, i, j: (h, i, 0)),
        ],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((g * bq, d), jnp.float32),
                        pltpu.VMEM((g * bq, 1), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, o, do, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(qf, kf, vf, g, bq, bk, scale, causal, interpret):
    return _forward(qf, kf, vf, g, bq, bk, scale, causal, interpret,
                    with_lse=False)


def _flash_fwd(qf, kf, vf, g, bq, bk, scale, causal, interpret):
    o, lse = _forward(qf, kf, vf, g, bq, bk, scale, causal, interpret,
                      with_lse=True)
    return o, (qf, kf, vf, o, lse)


def _flash_bwd(g, bq, bk, scale, causal, interpret, res, do):
    return _backward(*res, do, g, bq, bk, scale, causal, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


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
    """Returns (B, Hkv, G, S, D) attention output; differentiable through
    the dQ and dK/dV kernels.  Block sizes left at None come from
    ``default_blocks(S)``."""
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
    nq = s // bq
    bh = b * hkv

    # rows grouped as (G, bq) per q-block: reorder to (bh, nq*g*bq, d)
    qf = q.reshape(bh, g, nq, bq, d).transpose(0, 2, 1, 3, 4).reshape(
        bh, nq * g * bq, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    out = _flash(qf, kf, vf, g, bq, bk, scale, causal, interpret)

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

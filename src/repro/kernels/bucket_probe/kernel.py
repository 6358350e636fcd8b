"""Fused LSH bucket-probe Pallas TPU kernel: hash + searchsorted + sizes.

The per-step hot op of LGD sampling: given B query vectors, find for
every hash table t the contiguous slice [lo, hi) of the sorted-code
index that holds the query's bucket,

    lo[b, t] = #{ n : sorted_codes[t, n] <  code(q_b)[t] }
    hi[b, t] = #{ n : sorted_codes[t, n] <= code(q_b)[t] }

The XLA reference does this as (matmul, sign, pack) followed by an
L-way vmap of two ``searchsorted`` binary searches — O(log N) serial
gathers per table, a layout TPUs hate.  The kernel instead fuses

  1. the query projection matmul (B, d) @ (d, TW) on the MXU,
  2. the sign + bit-pack (``simhash.kernel.hash_tile``), and
  3. a *counting* probe: rank-by-comparison of each query code against
     the table's (1, BN) row of sorted codes, accumulated over N blocks

into one VMEM-resident pass.  Counting replaces the binary search with a
dense VPU reduction — O(N) work but contiguous reads and zero gathers.
The trade is explicit: the kernel streams all L*N sorted codes per call
(at HBM bandwidth, amortised over the B query batch), so it wins when
N/B is moderate and loses to O(log N) searchsorted when a huge index is
probed by few queries — ``core.tables.bucket_bounds_batched`` auto-
dispatches on exactly that ratio
(``COUNTING_PROBE_MAX_POINTS_PER_QUERY``).

MULTI-PROBE: the query codes are hashed ONCE per (B, L) tile; each
Hamming-ball probe mask is a compile-time XOR constant applied to the
base code, so the J-way probe walk reuses the same streamed sorted-code
tile — multi-probe costs no extra HBM traffic, only VPU compares.  The
single-probe kernel is the J = 1 case (mask 0).

Unsigned order trick: codes are uint32 but Mosaic comparisons are
cleanest in int32, so both sides are *biased* — ``c ^ 0x8000_0000``
reinterpreted as int32 preserves unsigned order exactly (the wrapper
biases ``sorted_codes`` once; the kernel biases the query codes it
computes).

Block layout (Mosaic blocks must be (8, 128)-aligned or whole dims, so
every small axis is the whole minor dim of its block):
  grid  = (B / BB, L / BL, N / BN)   — N innermost, sequential
  q     : (BB, d)        — query tile, reused across L and N steps
  w     : (d, TW)        — one lane-padded table tile of projections
  sc    : (BL, BN)       — biased int32 sorted-code tile
  lo/hi : (J, BB, BL)    — int32 counts of (L/BL, J, B, BL) outputs,
                           accumulated over N steps
  qc    : (BB, BL)       — scratch: biased query codes, computed at n==0
Counts are summed as f32 0/1 per block (exact below 2^24 >> BN) and
accumulated in int32 across blocks.

PERFORMANCE.  VMEM per step ~ 2*(BB*d + d*TW + BL*BN) words (double
buffered; ~4.5 MiB at d = 4096, BB = 8).  The projection matmul runs
once per (B, L) tile and is hidden behind the N-streaming steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..simhash.kernel import hash_tile

MAX_BB = 128       # query rows per tile
DEFAULT_BL = 8     # tables per tile
DEFAULT_BN = 512   # sorted-code columns per step

_BIAS = -(2 ** 31)   # int32 with only the sign bit set


def _i32(mask: int) -> int:
    """A uint32 constant as the int32 with the same bits."""
    return mask - (1 << 32) if mask >= (1 << 31) else mask


def _count_tile(qc, sc, n_off, n_actual):
    """Rank counts of qc (BB, BL) against the sc (BL, BN) tile.

    Returns (less, leq), each (BB, BL) int32.  One table per unrolled
    step: its code column (BB, 1) against its sorted row (1, BN).
    """
    bl, bn = sc.shape
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
             + n_off) < n_actual                         # mask N padding
    lane = jax.lax.broadcasted_iota(jnp.int32, qc.shape, 1)
    less = jnp.zeros(qc.shape, jnp.float32)
    leq = jnp.zeros(qc.shape, jnp.float32)
    for t in range(bl):
        q = qc[:, t:t + 1]                               # (BB, 1)
        s = sc[t:t + 1, :]                               # (1, BN)
        lt = jnp.sum(jnp.where((s < q) & valid, 1.0, 0.0),
                     axis=1, keepdims=True)
        le = jnp.sum(jnp.where((s <= q) & valid, 1.0, 0.0),
                     axis=1, keepdims=True)
        less = jnp.where(lane == t, lt, less)
        leq = jnp.where(lane == t, le, leq)
    return less.astype(jnp.int32), leq.astype(jnp.int32)


def _accumulate(base, sc_ref, lo_ref, hi_ref, masks, n_off, n_actual):
    sc = sc_ref[...]
    for j, mask in enumerate(masks):
        # XOR with the mask preserves the bias: (raw ^ bias) ^ mask ==
        # (raw ^ mask) ^ bias, since XOR commutes.
        qc = base ^ jnp.int32(_i32(mask)) if mask else base
        less, leq = _count_tile(qc, sc, n_off, n_actual)
        lo_ref[j] += less
        hi_ref[j] += leq


def _fused_kernel(q_ref, w_ref, pack_ref, sc_ref, lo_ref, hi_ref, qc_ref,
                  *, bn: int, n_actual: int, masks: tuple):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        qc_ref[...] = (hash_tile(q_ref[...], w_ref[...], pack_ref)
                       ^ jnp.int32(_BIAS))
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    _accumulate(qc_ref[...], sc_ref, lo_ref, hi_ref, masks, n_idx * bn,
                n_actual)


def _codes_kernel(qc_ref, sc_ref, lo_ref, hi_ref, *, bn: int, n_actual: int):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    _accumulate(qc_ref[...], sc_ref, lo_ref, hi_ref, (0,), n_idx * bn,
                n_actual)


def _outputs(n_lt: int, j: int, b: int, bb: int, bl: int):
    shape = jax.ShapeDtypeStruct((n_lt, j, b, bl), jnp.int32)
    spec = pl.BlockSpec((None, j, bb, bl), lambda i, t, n: (t, 0, i, 0))
    return [shape, shape], [spec, spec]


def bucket_probe_multi_pallas(
    q: jax.Array,             # (B, d) float32 queries, B % block_b == 0
    w_tiles: jax.Array,       # (L/BL, d, TW) tiled projections
    pack: jax.Array,          # (2, TW, BL) pack matrices
    sc_biased: jax.Array,     # (L, N) int32 biased sorted codes, N padded
    *,
    masks: tuple,
    n_actual: int,
    block_b: int,
    block_n: int,
    interpret: bool = False,
):
    """Fused hash + J-way Hamming-ball probe (J = 1: plain probe).

    Returns (lo, hi), each (L/BL, J, B, BL) int32: [t, j, b, c] is probe
    j's count for query b in table t*BL + c (``ops`` untangles it).  One
    streamed pass over the sorted codes serves all J probe codes.
    """
    b, d = q.shape
    n_lt, _, tw = w_tiles.shape
    bl = pack.shape[2]
    ll, n = sc_biased.shape
    assert ll == n_lt * bl and w_tiles.shape[1] == d, (
        q.shape, w_tiles.shape, sc_biased.shape)
    assert b % block_b == 0 and n % block_n == 0, (b, n, block_b, block_n)
    out_shape, out_specs = _outputs(n_lt, len(masks), b, block_b, bl)
    return pl.pallas_call(
        functools.partial(_fused_kernel, bn=block_n, n_actual=n_actual,
                          masks=tuple(masks)),
        grid=(b // block_b, n_lt, n // block_n),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, t, nn: (i, 0)),
            pl.BlockSpec((None, d, tw), lambda i, t, nn: (t, 0, 0)),
            pl.BlockSpec((2, tw, bl), lambda i, t, nn: (0, 0, 0)),
            pl.BlockSpec((bl, block_n), lambda i, t, nn: (t, nn)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_b, bl), jnp.int32)],
        interpret=interpret,
    )(q.astype(jnp.float32), w_tiles, pack, sc_biased)


def bucket_probe_codes_pallas(
    qc_biased: jax.Array,     # (L/BL, B, BL) int32 biased query codes
    sc_biased: jax.Array,     # (L, N) int32 biased sorted codes
    *,
    n_actual: int,
    block_b: int,
    block_n: int,
    interpret: bool = False,
):
    """Probe-only variant for codes hashed outside the kernel (quadratic
    SRP's per-function quadratic forms, banded probe codes).  Returns
    (lo, hi), each (L/BL, 1, B, BL) int32."""
    n_lt, b, bl = qc_biased.shape
    ll, n = sc_biased.shape
    assert ll == n_lt * bl, (qc_biased.shape, sc_biased.shape)
    assert b % block_b == 0 and n % block_n == 0, (b, n, block_b, block_n)
    out_shape, out_specs = _outputs(n_lt, 1, b, block_b, bl)
    return pl.pallas_call(
        functools.partial(_codes_kernel, bn=block_n, n_actual=n_actual),
        grid=(b // block_b, n_lt, n // block_n),
        in_specs=[
            pl.BlockSpec((None, block_b, bl), lambda i, t, nn: (t, i, 0)),
            pl.BlockSpec((bl, block_n), lambda i, t, nn: (t, nn)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(qc_biased, sc_biased)

"""Fused SimHash Pallas TPU kernel: projection matmul + sign + bit-pack.

The hot op of LGD's preprocessing/refresh path: hash every training point
(N can be 1e5..1e9 across data shards) into L packed K-bit codes,

    codes[n, t] = sum_k (x[n] @ w[:, t*K + k] >= 0) << k        (uint32)

HARDWARE ADAPTATION (vs. the paper's CPU sparse projections): on TPU the
MXU makes a *dense* (BN, d) @ (d, TW) tile matmul essentially free
compared to the HBM traffic of streaming X, so instead of sparse
multiplications we fuse the full projection, the sign, and the bit-pack
into one VMEM-resident pass — one read of X, one tiny write of codes
(32x smaller than the projection output it replaces).  The pack is a
second MXU dot of the 0/1 bits with a constant power-of-two matrix.

Tile layout (Mosaic blocks must be (8, 128)-aligned or whole dims):
  * tables are processed BL at a time; table tile j's BL*K projection
    columns are zero-padded to TW = round_up(BL*K, 128) lanes
    (``tile_projections``: (L/BL, d, TW)), so the projection block is
    lane-dense for any K;
  * the pack matrix (``pack_matrices``: (2, TW, BL)) maps bit k of table
    t to 2^(k mod 16) in half k // 16 — both halves are sums of distinct
    powers of two below 2^16, exact in f32 whatever the MXU's input
    precision, and the two halves assemble any K <= 32 exactly;
    zero-padded columns have all-zero pack rows and never count;
  * codes come out as (L/BL, N, BL) int32 — the block's minor dim is the
    whole BL axis — and the wrapper transposes them to (N, L) uint32.

  grid  = (L/BL, N/BN)   — tables outer, so a projection tile stays
                           resident while the points stream past it
  x     : (BN, d)        — BN sized so the block stays ~2 MiB
  w     : (d, TW)        — one table tile's projections
  codes : (BN, BL)       — int32 bit patterns of the uint32 codes

The projection runs at ``Precision.HIGHEST`` (as does the XLA reference),
so a code bit differs between the two only when a projection lies within
f32 summation-order rounding of zero.

FALLBACK CONTRACT.  ``ops.simhash_codes(use_pallas=False)`` lowers to
``ref.simhash_codes_ref``; ``use_pallas=True, interpret=True`` runs this
kernel under the Pallas interpreter and is the parity surface CI pins on
CPU.  Callers auto-dispatch via ``repro.kernels.default_use_pallas()``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import round_up

DEFAULT_BL = 8                 # tables per tile
MAX_BN = 256                   # point rows per block
_X_BLOCK_BYTES = 2 << 20       # VMEM budget for one (BN, d) f32 block
_LANE = 128


def block_rows(d: int, cap: int = MAX_BN) -> int:
    """Rows per (rows, d) f32 block: a multiple of 8 within the budget."""
    return max(8, min(cap, _X_BLOCK_BYTES // (4 * d)) // 8 * 8)


def tile_width(k: int, bl: int) -> int:
    return round_up(bl * k, _LANE)


def tile_projections(w: jax.Array, *, k: int, bl: int) -> jax.Array:
    """(d, L*K) projections -> (L/BL, d, TW) lane-padded table tiles."""
    d, lk = w.shape
    n_lt = lk // (bl * k)
    w3 = w.astype(jnp.float32).reshape(d, n_lt, bl * k).transpose(1, 0, 2)
    return jnp.pad(w3, ((0, 0), (0, 0), (0, tile_width(k, bl) - bl * k)))


def pack_matrices(k: int, bl: int) -> jax.Array:
    """(2, TW, BL) f32: [h, t*K + b, t] = 2^(b - 16h) for bit b in half h."""
    m = np.zeros((2, tile_width(k, bl), bl), np.float32)
    for t in range(bl):
        for b in range(k):
            m[b // 16, t * k + b, t] = float(2 ** (b % 16))
    return jnp.asarray(m)


def hash_tile(x: jax.Array, w: jax.Array, pack_ref) -> jax.Array:
    """In-kernel hash of a (R, d) block by one (d, TW) table tile.

    Returns (R, BL) int32: the bit patterns of the packed uint32 codes.
    """
    proj = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                # (R, TW)
    bits = jnp.where(proj >= 0.0, 1.0, 0.0)
    lo = jnp.dot(bits, pack_ref[0], preferred_element_type=jnp.float32)
    hi = jnp.dot(bits, pack_ref[1], preferred_element_type=jnp.float32)
    return lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)


def _simhash_kernel(x_ref, w_ref, pack_ref, out_ref):
    out_ref[...] = hash_tile(x_ref[...], w_ref[...], pack_ref)


def simhash_codes_pallas(
    x: jax.Array,
    w_tiles: jax.Array,
    pack: jax.Array,
    *,
    block_n: int,
    interpret: bool = False,
) -> jax.Array:
    """Packed SimHash code bits for a batch of points.

    x: (N, d) f32 with N % block_n == 0; w_tiles: (L/BL, d, TW) from
    ``tile_projections``; pack: (2, TW, BL) from ``pack_matrices``.
    Returns (L/BL, N, BL) int32 (``ops.simhash_codes`` untangles it).
    """
    n, d = x.shape
    n_lt, dw, tw = w_tiles.shape
    bl = pack.shape[2]
    assert dw == d and pack.shape[1] == tw and n % block_n == 0, (
        x.shape, w_tiles.shape, pack.shape, block_n)
    return pl.pallas_call(
        _simhash_kernel,
        grid=(n_lt, n // block_n),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((None, d, tw), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((2, tw, bl), lambda j, i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_n, bl), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_lt, n, bl), jnp.int32),
        interpret=interpret,
    )(x, w_tiles, pack)

"""Fused gather+weight Pallas TPU kernel: token-row gather + 1/(p·N) weights.

The last host-resident op of the LGD step path: Algorithm 1 emits m
sampled example ids and their exact probabilities; the batch the trainer
consumes is the gathered token rows plus the importance weights

    w_j = 1 / (max(p_j, p_floor) * N)

that de-bias the adaptive draw.  Before this kernel the gather ran on
the host (``np.asarray`` per batch — a device->host->device round-trip
every step); here the token store stays resident in HBM and the whole
batch assembly is one kernel launch appended to the step's program.

HARDWARE ADAPTATION.  A row gather with data-dependent row ids cannot be
expressed with static BlockSpecs alone — the block index must be
computed from the sampled ids.  This is the canonical scalar-prefetch
pattern: the ids are a ``PrefetchScalarGridSpec`` scalar operand (SMEM),
and the index_map of the token store reads ``idx_ref[i]``.  HBM arrays
are tiled in (8, 128) blocks, so a one-row block cannot be DMA'd on its
own: the store is viewed as (N/8, 8, S) — a free reshape of the same
bytes — and grid step i fetches the aligned 8-row tile holding row
idx[i], then copies row idx[i] % 8 into output row i.  The weights are
computed once, at step 0, from the whole (m, 1) probability block.

Block layout:
  grid   = (m,)                  — one sampled row per step
  idx    : (m,) int32            — scalar-prefetch operand (SMEM)
  probs  : (m, 1) f32            — whole array, resident
  store  : (8, S) int32          — the tile of (N/8, 8, S) holding idx[i]
  rows   : (1, S) int32          — row i of the (m, 1, S) output
  w      : (m, 1) f32            — whole array, written at step 0

m is a minibatch (8..512); the per-step VMEM footprint is one 8-row
tile, and the m DMAs are issued back-to-back by the pipelined grid.  The
XLA reference (``ref.py``) is ``store[idx]`` + the same arithmetic, and
the path CPU hosts auto-dispatch to (see ``ops.gather_weight``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS_PER_TILE = 8     # sublane tile of 32-bit arrays


def _gather_weight_kernel(idx_ref, probs_ref, tile_ref, rows_ref, w_ref,
                          *, n_points: int, p_floor: float):
    i = pl.program_id(0)
    r = idx_ref[i] % ROWS_PER_TILE
    rows_ref[...] = tile_ref[pl.ds(r, 1), :]

    @pl.when(i == 0)
    def _weights():
        p = jnp.maximum(probs_ref[...], p_floor)
        w_ref[...] = 1.0 / (p * n_points)


def gather_weight_pallas(
    store: jax.Array,       # (N, S) int32 token rows, N % 8 == 0
    idx: jax.Array,         # (m,) int32 sampled row ids
    probs: jax.Array,       # (m, 1) f32 Algorithm-1 probabilities
    *,
    n_points: int,
    p_floor: float,
    interpret: bool = False,
):
    """Fused gather+weight: returns (rows (m, S) int32, w (m, 1) f32).

    ``n_points`` is the N of the weights (the store may carry padding
    rows beyond it)."""
    n, s = store.shape
    m = idx.shape[0]
    assert probs.shape == (m, 1) and n % ROWS_PER_TILE == 0, (
        probs.shape, m, n)
    tiles = store.reshape(n // ROWS_PER_TILE, ROWS_PER_TILE, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((m, 1), lambda i, idx_ref: (0, 0)),
            pl.BlockSpec((None, ROWS_PER_TILE, s),
                         lambda i, idx_ref: (idx_ref[i] // ROWS_PER_TILE,
                                             0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, s), lambda i, idx_ref: (i, 0, 0)),
            pl.BlockSpec((m, 1), lambda i, idx_ref: (0, 0)),
        ],
    )
    rows, w = pl.pallas_call(
        functools.partial(_gather_weight_kernel, n_points=n_points,
                          p_floor=p_floor),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, 1, s), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(idx.astype(jnp.int32), probs.astype(jnp.float32), tiles)
    return rows.reshape(m, s), w

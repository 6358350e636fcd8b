"""Jit'd public wrapper for the SimHash kernel (padding + dispatch)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import round_up as _round_up
from .kernel import (
    DEFAULT_BL,
    block_rows,
    pack_matrices,
    simhash_codes_pallas,
    tile_projections,
)
from .ref import simhash_codes_ref


@partial(jax.jit, static_argnames=("k", "l", "use_pallas", "interpret"))
def simhash_codes(
    x: jax.Array,
    w: jax.Array,
    *,
    k: int,
    l: int,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Packed SimHash codes (N, L) uint32; pads N and L to block multiples.

    ``use_pallas=False`` falls back to the jnp oracle (used on CPU hosts
    where the interpreter would be slower than XLA:CPU).
    """
    if not use_pallas:
        return simhash_codes_ref(x, w, k=k, l=l)
    n, d = x.shape
    bn = min(block_rows(d), _round_up(n, 8))
    bl = min(DEFAULT_BL, l)
    n_pad = _round_up(n, bn)
    l_pad = _round_up(l, bl)
    xp = jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, (l_pad - l) * k)))
    codes = simhash_codes_pallas(
        xp, tile_projections(wp, k=k, bl=bl), pack_matrices(k, bl),
        block_n=bn, interpret=interpret)                 # (L/BL, N, BL)
    codes = codes.transpose(1, 0, 2).reshape(n_pad, l_pad)[:n, :l]
    return jax.lax.bitcast_convert_type(codes, jnp.uint32)

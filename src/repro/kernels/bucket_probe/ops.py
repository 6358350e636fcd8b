"""Jit'd public wrappers for the bucket-probe kernel (padding + dispatch).

Contract: ``use_pallas=False`` (the CPU-host default chosen by callers)
runs the pure-XLA oracle; ``use_pallas=True, interpret=True`` runs the
kernel under the Pallas interpreter and must match the oracle exactly —
that is the parity surface the tests pin down.  Padding keeps arbitrary
(B, L, N) shapes legal: B and L are padded to block multiples (padded
rows/tables are computed then sliced off), N is padded to a block
multiple and masked *inside* the kernel so padded columns never count.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import round_up as _round_up
from ..simhash.kernel import block_rows, pack_matrices, tile_projections
from .kernel import (
    DEFAULT_BL,
    DEFAULT_BN,
    MAX_BB,
    bucket_probe_codes_pallas,
    bucket_probe_multi_pallas,
)
from .ref import (
    bucket_probe_codes_ref,
    bucket_probe_multi_ref,
    bucket_probe_ref,
)


def _bias(codes_u32: jax.Array) -> jax.Array:
    """uint32 -> order-preserving int32 (toggle the sign bit)."""
    return jax.lax.bitcast_convert_type(
        codes_u32 ^ jnp.uint32(0x80000000), jnp.int32)


def _blocks(b: int, l: int, n: int, d: int = 0):
    """(BB, BL, BN) and the padded (B, L, N) they tile."""
    bb = min(MAX_BB, block_rows(d) if d else MAX_BB, _round_up(b, 8))
    bl = min(DEFAULT_BL, l)
    bn = min(DEFAULT_BN, _round_up(n, 128))
    return (bb, bl, bn), (_round_up(b, bb), _round_up(l, bl), _round_up(n, bn))


def _pad_sc(sorted_codes: jax.Array, l_pad: int, n_pad: int) -> jax.Array:
    l, n = sorted_codes.shape
    sc = jnp.pad(sorted_codes, ((0, l_pad - l), (0, n_pad - n)))
    return _bias(sc)


def _unblock(a: jax.Array, b: int, l: int) -> jax.Array:
    """(L/BL, J, B_pad, BL) kernel counts -> (B, J, L)."""
    n_lt, j, b_pad, bl = a.shape
    return a.transpose(2, 1, 0, 3).reshape(b_pad, j, n_lt * bl)[:b, :, :l]


def _check(q, w, sorted_codes, k, l):
    if w.shape != (q.shape[1], l * k):
        raise ValueError(
            f"projections {w.shape} != (d={q.shape[1]}, L*K={l * k})")
    if sorted_codes.shape[0] != l:
        raise ValueError(
            f"sorted_codes {sorted_codes.shape} has {sorted_codes.shape[0]} "
            f"tables, expected L={l}")


def _fused(q, w, sorted_codes, masks, k, l, interpret):
    """(B, J, L) bounds through the fused hash + probe kernel."""
    b, d = q.shape
    n = sorted_codes.shape[1]
    (bb, bl, bn), (b_pad, l_pad, n_pad) = _blocks(b, l, n, d)
    lo, hi = bucket_probe_multi_pallas(
        jnp.pad(q, ((0, b_pad - b), (0, 0))),
        tile_projections(jnp.pad(w, ((0, 0), (0, (l_pad - l) * k))),
                         k=k, bl=bl),
        pack_matrices(k, bl),
        _pad_sc(sorted_codes, l_pad, n_pad),
        masks=tuple(masks), n_actual=n, block_b=bb, block_n=bn,
        interpret=interpret)
    return _unblock(lo, b, l), _unblock(hi, b, l)


@partial(jax.jit, static_argnames=("k", "l", "use_pallas", "interpret"))
def bucket_probe(
    q: jax.Array,             # (B, d) or (d,) query vectors
    w: jax.Array,             # (d, L*K) projections
    sorted_codes: jax.Array,  # (L, N) uint32, ascending per row
    *,
    k: int,
    l: int,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Fused hash+probe -> (lo, hi) int32, (B, L) (or (L,) for 1-D q)."""
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    _check(q, w, sorted_codes, k, l)
    if not use_pallas:
        lo, hi = bucket_probe_ref(q, w, sorted_codes, k=k, l=l)
    else:
        lo, hi = _fused(q, w, sorted_codes, (0,), k, l, interpret)
        lo, hi = lo[:, 0], hi[:, 0]
    return (lo[0], hi[0]) if squeeze else (lo, hi)


@partial(jax.jit, static_argnames=("masks", "k", "l", "use_pallas",
                                   "interpret"))
def bucket_probe_multi(
    q: jax.Array,             # (B, d) or (d,) query vectors
    w: jax.Array,             # (d, L*K) projections
    sorted_codes: jax.Array,  # (L, N) uint32, ascending per row
    masks: tuple,             # J static XOR masks (probe_masks(k, J))
    *,
    k: int,
    l: int,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Fused hash + multi-probe: (lo, hi) int32, (B, J, L) (or (J, L)).

    For each query, table, and Hamming-ball probe mask, the [lo, hi)
    slice of the bucket whose code is ``code(q)[t] ^ masks[j]``.  The
    kernel hashes once and reuses the streamed sorted-code tile for all
    J probe codes; the XLA reference path (``use_pallas=False``) lowers
    to hash + J*L binary searches.  Parity between the two is pinned by
    tests/test_multiprobe.py.
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    _check(q, w, sorted_codes, k, l)
    if not use_pallas:
        lo, hi = bucket_probe_multi_ref(q, w, sorted_codes, masks, k=k, l=l)
    else:
        lo, hi = _fused(q, w, sorted_codes, masks, k, l, interpret)
    return (lo[0], hi[0]) if squeeze else (lo, hi)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def bucket_probe_codes(
    qcodes: jax.Array,        # (B, L) or (L,) uint32 query codes
    sorted_codes: jax.Array,  # (L, N) uint32, ascending per row
    *,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Probe-only entry point (pre-hashed queries, e.g. quadratic SRP)."""
    squeeze = qcodes.ndim == 1
    if squeeze:
        qcodes = qcodes[None]
    if not use_pallas:
        lo, hi = bucket_probe_codes_ref(qcodes, sorted_codes)
    else:
        b, l = qcodes.shape
        n = sorted_codes.shape[1]
        (bb, bl, bn), (b_pad, l_pad, n_pad) = _blocks(b, l, n)
        qc = jnp.pad(_bias(qcodes), ((0, b_pad - b), (0, l_pad - l)))
        lo, hi = bucket_probe_codes_pallas(
            qc.reshape(b_pad, l_pad // bl, bl).transpose(1, 0, 2),
            _pad_sc(sorted_codes, l_pad, n_pad),
            n_actual=n, block_b=bb, block_n=bn, interpret=interpret,
        )
        lo, hi = _unblock(lo, b, l)[:, 0], _unblock(hi, b, l)[:, 0]
    return (lo[0], hi[0]) if squeeze else (lo, hi)

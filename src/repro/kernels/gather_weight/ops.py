"""Jit'd public wrapper for the gather+weight kernel (padding + dispatch).

Contract: ``use_pallas=False`` (the CPU-host default chosen by callers)
runs the pure-XLA oracle; ``use_pallas=True, interpret=True`` runs the
kernel under the Pallas interpreter and must match the oracle exactly —
that is the parity surface pinned by tests/test_gather_weight.py.  The
row width is padded to a lane multiple (padded columns are sliced off;
they are gathered but never observed), so arbitrary sequence lengths
are legal.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import round_up as _round_up
from .kernel import ROWS_PER_TILE, gather_weight_pallas
from .ref import gather_weight_ref

_LANE = 128


@partial(jax.jit, static_argnames=("p_floor", "use_pallas", "interpret"))
def gather_weight(
    store: jax.Array,   # (N, S) int32 device-resident token rows
    idx: jax.Array,     # (m,) int32 sampled row ids
    probs: jax.Array,   # (m,) f32 Algorithm-1 probabilities
    *,
    p_floor: float = 1e-8,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Fused batch assembly: (rows (m, S) int32, weights (m,) f32)."""
    if idx.shape != probs.shape or idx.ndim != 1:
        raise ValueError(
            f"idx {idx.shape} and probs {probs.shape} must be matching "
            "1-D arrays")
    if not use_pallas:
        return gather_weight_ref(store, idx, probs, p_floor=p_floor)
    n, s = store.shape
    # hot-path note: callers on the kernel path should hand in a store
    # whose row width is a lane multiple and whose row count is a
    # sublane-tile multiple (the LGD pipeline pads its device store ONCE
    # at build) — then this pad is zero-width and compiles away; an
    # unpadded store still works but costs an O(N*S) copy per call.
    s_pad = _round_up(s, _LANE)
    n_pad = _round_up(n, ROWS_PER_TILE)
    rows, w = gather_weight_pallas(
        jnp.pad(store, ((0, n_pad - n), (0, s_pad - s))),
        idx, probs[:, None],
        n_points=n, p_floor=p_floor, interpret=interpret)
    return rows[:, :s], w[:, 0]

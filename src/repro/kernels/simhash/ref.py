"""Pure-jnp oracle for the SimHash kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def simhash_codes_ref(x: jax.Array, w: jax.Array, *, k: int, l: int) -> jax.Array:
    """codes[n, t] = sum_k (x[n] @ w[:, t*K+k] >= 0) << k  — (N, L) uint32.

    The projection runs at ``Precision.HIGHEST`` like the kernel's: TPU's
    default f32 matmul rounds inputs to bf16, which would flip the sign
    of small projections (a no-op on CPU, where f32 matmuls are exact).
    """
    proj = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)       # (N, L*K)
    bits = (proj >= 0).reshape(x.shape[0], l, k).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(k, dtype=jnp.uint32)
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)

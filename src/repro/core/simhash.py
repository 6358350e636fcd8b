"""SimHash parameters, projections, packed codes and probe masks.

The paper (Chen, Xu & Shrivastava, NeurIPS 2019) samples training points
with probability monotonic to |<[theta,-1], [x_i,y_i]>| using SimHash
(signed random projections).  WHICH hash family is in play — symmetric
SRP (dense/sparse projections), quadratic SRP over T(v)=vec(v vᵀ), or
the asymmetric Simple-LSH MIPS family — is pluggable: the contract and
registry live in ``core.families``; ``LSHParams.family`` names a
registry entry, and this module draws the matching projection tensor
and packs codes in the shared TPU-native layout.

All families pack ``code_width(K)`` sign bits per table into a single
uint32 code, giving ``codes[n, l]`` — the layout consumed by
``tables.py``.  The closed-form collision probabilities are owned by
the family objects; ``collision_probability`` (SRP cosine law) and
``collision_probability_quadratic`` are re-exported here for
back-compat with the pre-family API.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .families import (  # noqa: F401  (re-exported: pre-family API)
    get_family,
    quadratic_collision_prob as collision_probability_quadratic,
    srp_collision_prob as collision_probability,
)

MAX_K = 32  # sign bits packed per uint32 code


@dataclasses.dataclass(frozen=True)
class LSHParams:
    """Static hyper-parameters of the hash family."""

    k: int = 5          # bits (hash fns) per table    (paper: K=5 linear, 7 BERT)
    l: int = 100        # number of hash tables        (paper: L=100 linear, 10 BERT)
    dim: int = 0        # input dimensionality (of the *augmented* vector)
    family: str = "sparse"  # registry key: core.families.get_family
    sparsity: float = 1.0 / 30.0  # density of sparse projections
    seed: int = 0

    def __post_init__(self):
        fam = get_family(self.family)   # raises on unknown family names
        if not (1 <= fam.code_width(self.k) <= MAX_K):
            raise ValueError(
                f"code width must be in [1,{MAX_K}], got "
                f"{fam.code_width(self.k)} (K={self.k})")
        if self.l < 1:
            raise ValueError(f"L must be >= 1, got {self.l}")


def make_projections(key: jax.Array, params: LSHParams) -> jax.Array:
    """Draw the random projection tensor for the family.

    Returns (by the family's ``proj_kind``)
      dense/sparse:  (dim, L*K) float32
      quadratic:     (L*K, dim, dim) float32  (random M per hash function)

    ``params.dim`` is the dimensionality of the AUGMENTED vectors the
    family actually hashes (asymmetric families: ``aug_dim(d_raw)``).
    """
    fam = get_family(params.family)
    proj_kind = fam.proj_kind
    d, lk = params.dim, params.l * params.k
    if proj_kind == "dense":
        # mask_projections: identity for flat families; the banded MIPS
        # family zeroes the band coordinate's row so hashing sees only
        # the Simple-LSH geometry (core.families.base).
        return fam.mask_projections(
            jax.random.normal(key, (d, lk), dtype=jnp.float32))
    if proj_kind == "sparse":
        kv, ks = jax.random.split(key)
        signs = jax.random.rademacher(kv, (d, lk), dtype=jnp.float32)
        mask = jax.random.bernoulli(ks, params.sparsity, (d, lk))
        # Li et al. very-sparse projections: scale keeps inner products unbiased.
        return signs * mask / jnp.sqrt(params.sparsity)
    # quadratic: M_h ~ dense iid Gaussian (d, d); hash = sign(v^T M v), which
    # is exactly SRP on T(v)=vec(v v^T).  Sparse M would bias the analytic
    # collision probability (T(v) is highly structured), so the exact
    # importance weights 1/(p_i N) demand dense projections here.
    return jax.random.normal(key, (lk, d, d), dtype=jnp.float32)


def _pack_bits(bits: jax.Array, k: int) -> jax.Array:
    """bits: (..., L, K) bool -> (..., L) uint32 packed codes."""
    weights = (jnp.uint32(1) << jnp.arange(k, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("k", "l", "quadratic"))
def compute_codes(
    x: jax.Array,
    projections: jax.Array,
    *,
    k: int,
    l: int,
    quadratic: bool = False,
) -> jax.Array:
    """Hash a batch of vectors into packed per-table codes.

    x: (n, d) or (d,).  Returns (n, L) or (L,) uint32.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    if quadratic:
        # proj[h] = x^T M_h x  — implicit SRP over T(x)=vec(x x^T).
        proj = jnp.einsum("nd,hde,ne->nh", x, projections, x,
                          precision=jax.lax.Precision.HIGHEST)
    else:
        # HIGHEST: the same f32 projection the hashing kernels compute
        # (TPU's default f32 matmul rounds inputs to bf16; CPU is exact)
        proj = jnp.dot(x, projections,
                       precision=jax.lax.Precision.HIGHEST)  # (n, L*K)
    bits = (proj >= 0).reshape(x.shape[0], l, k)
    codes = _pack_bits(bits, k)
    return codes[0] if squeeze else codes


def probe_masks(k: int, n_codes: int) -> tuple:
    """Deterministic Hamming-ball probe sequence for multi-probe querying.

    Returns a tuple of ``n_codes`` XOR masks over the packed K-bit code,
    walked in order when a probed bucket is empty: the exact bucket
    (mask 0) first, then all flip-1 masks (ascending bit index), then
    all flip-2 masks (lexicographic bit pairs).  ``n_codes`` is clamped
    to the Hamming-ball-of-radius-2 size ``1 + K + K(K-1)/2``.

    Args:
      k: bits per table code (``LSHParams.k``).
      n_codes: total probe codes per table INCLUDING the exact bucket
        (``1 + multiprobe`` in sampler terms).

    Returns:
      Tuple of Python ints (static — safe as a jit-static argument).

    Determinism: the sequence is a pure function of ``k`` and
    ``n_codes``; the corrected sampling probability depends on the
    probed masks only through their popcounts, so any truncation of
    this sequence still yields exact probabilities (see
    ``core.sampler``).
    """
    if n_codes < 1:
        raise ValueError(f"n_codes must be >= 1, got {n_codes}")
    masks = [0]
    masks.extend(1 << i for i in range(k))
    masks.extend(
        (1 << i) | (1 << j) for i in range(k) for j in range(i + 1, k))
    return tuple(masks[:n_codes])


def augment_regression(x: jax.Array, y: jax.Array) -> jax.Array:
    """[x_i, y_i] augmentation for least squares (Eq. 4), L2-normalised rows."""
    v = jnp.concatenate([x, y[..., None]], axis=-1)
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-30)


def regression_query(theta: jax.Array) -> jax.Array:
    """Query vector [theta, -1] for least squares."""
    return jnp.concatenate([theta, -jnp.ones(theta.shape[:-1] + (1,), theta.dtype)], -1)


def augment_logistic(x: jax.Array, y: jax.Array) -> jax.Array:
    """y_i * x_i augmentation for logistic regression (Sec. 2.3), normalised."""
    v = x * y[..., None]
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-30)


def logistic_query(theta: jax.Array) -> jax.Array:
    """Query -theta for logistic regression (Eq. 20)."""
    return -theta

"""Inputs and weights made from ``--seed``, on the device.

The corpus follows the program's synthetic LM corpus: tokens from a Zipf
law over the vocabulary, with a ``hard_frac`` share of rows drawn from the
same law over a permuted vocabulary.  It is drawn on the device by inverse
CDF, so a 1024 x 4097 shard costs one small program, not a host loop.

The model's weights are drawn by the ``make_params`` of the configuration's
architecture module (``chipbench/arch/<model_type>.py``), exactly as the
program's initialiser lays them out; the benchmark's tests pin the two
equal, and the reference draws its own copy from there, never from the
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# stream of each consumer of the run's root key
CORPUS, PARAMS, PIPELINE = 1, 2, 3


def root_key(seed: int):
    """A key for any whole seed, including ones past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def stream(seed: int, which: int):
    return jax.random.fold_in(root_key(seed), which)


@functools.partial(jax.jit, static_argnames=("rows", "seq", "vocab",
                                             "zipf", "hard_frac"))
def make_corpus(key, *, rows, seq, vocab, zipf, hard_frac):
    """(rows, seq + 1) int32 tokens and the (rows,) hard-row mask."""
    ke, kh, kp, km = jax.random.split(key, 4)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** -zipf
    cdf = jnp.cumsum(probs / jnp.sum(probs))

    def draw(k):
        u = jax.random.uniform(k, (rows, seq + 1)) * cdf[-1]
        return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                           vocab - 1).astype(jnp.int32)

    perm = jax.random.permutation(kp, vocab).astype(jnp.int32)
    hard = jax.random.uniform(km, (rows,)) < hard_frac
    tokens = jnp.where(hard[:, None], perm[draw(kh)], draw(ke))
    return tokens, hard


def corpus_for(seed: int, cfg, traffic: dict):
    return make_corpus(
        stream(seed, CORPUS), rows=traffic["corpus_rows"],
        seq=traffic["seq"], vocab=cfg.vocab,
        zipf=float(traffic["zipf_exponent"]),
        hard_frac=float(traffic["hard_frac"]))


def params_on(arch, key, cfg, shardings):
    """``arch.make_params`` as one jitted call straight into ``shardings``."""
    return jax.jit(arch.make_params, static_argnums=1,
                   out_shardings=shardings)(key, cfg)

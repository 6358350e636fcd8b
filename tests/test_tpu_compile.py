"""Every main-path Pallas kernel compiles for a TPU v5e at real widths.

The interpret-mode parity tests cannot see what Mosaic refuses (blocks
not aligned to the (8, 128) tiling, too much VMEM), so these tests run
the TPU compiler on a described, not attached, v5e chip
(``jax.experimental.topologies``) at the LGD pipeline's sizes — feature
width d = 4096, a 2048-row shard, K = 7, L = 10 — and at Granite-3-8B's
attention shapes, and check that the kernel is in the program.  Nothing
runs, so this says nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, N, K, L, SEQ = 4096, 2048, 7, 10, 4096
BALL = 1 + K + K * (K - 1) // 2   # the whole radius-2 probe sequence
ROW = 4096 + 128          # a 4097-token row, lane-padded


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


def _simhash(dev):
    from repro.kernels.simhash import simhash_codes
    fn = lambda x, w: simhash_codes(x, w, k=K, l=L, use_pallas=True)  # noqa
    return fn, _shapes(dev, ((N, D), jnp.float32), ((D, L * K), jnp.float32))


def _probe(dev):
    from repro.kernels.bucket_probe import bucket_probe
    fn = lambda q, w, sc: bucket_probe(  # noqa: E731
        q, w, sc, k=K, l=L, use_pallas=True)
    return fn, _shapes(dev, ((1, D), jnp.float32),
                       ((D, L * K), jnp.float32), ((L, N), jnp.uint32))


def _probe_multi(dev):
    from repro.core.simhash import probe_masks
    from repro.kernels.bucket_probe import bucket_probe_multi
    masks = probe_masks(K, BALL)
    fn = lambda q, w, sc: bucket_probe_multi(  # noqa: E731
        q, w, sc, masks, k=K, l=L, use_pallas=True)
    return fn, _shapes(dev, ((1, D), jnp.float32),
                       ((D, L * K), jnp.float32), ((L, N), jnp.uint32))


def _probe_banded(dev):
    from repro.core import LSHParams
    from repro.core.simhash import probe_masks
    from repro.core.tables import LSHIndex, bucket_bounds_banded
    p = LSHParams(k=K, l=L, dim=D + 1, family="mips_banded")
    masks = probe_masks(K, BALL)

    def fn(proj, sc, order, q):
        return bucket_bounds_banded(LSHIndex(proj, sc, order), q, p, masks,
                                    use_pallas=True)
    return fn, _shapes(dev, ((D + 1, L * K), jnp.float32),
                       ((L, N), jnp.uint32), ((L, N), jnp.int32),
                       ((1, D + 1), jnp.float32))


def _gather_weight(dev):
    from repro.kernels.gather_weight import gather_weight
    fn = lambda st, i, p: gather_weight(st, i, p, use_pallas=True)  # noqa
    return fn, _shapes(dev, ((N, ROW), jnp.int32), ((4,), jnp.int32),
                       ((4,), jnp.float32))


def _flash_attention(dev):
    from repro.kernels.flash_attention import gqa_attention
    fn = lambda q, k, v: gqa_attention(q, k, v, use_pallas=True)  # noqa
    return fn, _shapes(dev, ((1, SEQ, 32, 128), jnp.bfloat16),
                       ((1, SEQ, 8, 128), jnp.bfloat16),
                       ((1, SEQ, 8, 128), jnp.bfloat16))


def _flash_decode(dev):
    from repro.kernels.flash_attention import gqa_decode
    fn = lambda q, k, v, n: gqa_decode(q, k, v, n, use_pallas=True)  # noqa
    return fn, _shapes(dev, ((2, 1, 32, 128), jnp.bfloat16),
                       ((2, SEQ, 8, 128), jnp.bfloat16),
                       ((2, SEQ, 8, 128), jnp.bfloat16), ((2,), jnp.int32))


CASES = {
    "simhash": _simhash,
    "bucket_probe": _probe,
    "bucket_probe_multi": _probe_multi,
    "bucket_probe_banded": _probe_banded,
    "gather_weight": _gather_weight,
    "flash_attention": _flash_attention,
    "flash_decode": _flash_decode,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), case

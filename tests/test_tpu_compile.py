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
import re

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


def _granite_one_chip(dev):
    from repro.configs import granite_3_8b
    from repro.models import init_params
    cfg = granite_3_8b.ONE_CHIP
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), params)
    tokens = jax.ShapeDtypeStruct((2, SEQ), jnp.int32, sharding=dev)
    return cfg, params, tokens


def _kernel_results(text):
    """The result type of each ``tpu_custom_call`` in a compiled program."""
    return re.findall(r"= (\(.*?\)|\S+) custom-call\([^\n]*"
                      r'custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("program", ["refresh_embed", "loss_and_grad"])
def test_granite_attention_path_on_v5e(one_chip, monkeypatch, program):
    """On one TPU device the refresh embed runs its attention through the
    flash kernel and still compiles as ``jit_refresh_embed``; the loss
    and its gradient run it too, with its dQ and dK/dV kernels."""
    import repro.kernels
    from repro.data import mean_pool_feature_fn
    from repro.models import loss
    monkeypatch.setattr(repro.kernels, "default_use_pallas", lambda: True)
    cfg, params, tokens = _granite_one_chip(one_chip)
    if program == "refresh_embed":
        lowered = mean_pool_feature_fn(cfg).lower(params, tokens)
    else:
        lowered = jax.jit(lambda p, b: jax.value_and_grad(loss)(p, cfg, b)
                          ).lower(params, {"tokens": tokens, "targets": tokens})
    text = lowered.compile().as_text()
    if program == "refresh_embed":
        assert text.startswith("HloModule jit_refresh_embed")
    assert "tpu_custom_call" in text


def test_refresh_embed_runs_the_primal_kernel_on_v5e(one_chip, monkeypatch):
    """Nothing differentiates the refresh embed, so its one kernel is the
    forward alone: a single bf16 result, no log-sum-exp output, as before
    the kernel had a backward.  The gradient's forward adds the f32
    log-sum-exp (B*Hkv, G*S, 128)."""
    import repro.kernels
    from repro.data import mean_pool_feature_fn
    from repro.models import loss
    monkeypatch.setattr(repro.kernels, "default_use_pallas", lambda: True)
    cfg, params, tokens = _granite_one_chip(one_chip)
    embed = _kernel_results(mean_pool_feature_fn(cfg).lower(
        params, tokens).compile().as_text())
    assert len(embed) == 1 and embed[0].startswith("bf16[16,16384,128]"), \
        embed
    grad = _kernel_results(jax.jit(
        lambda p, b: jax.value_and_grad(loss)(p, cfg, b)).lower(
            params, {"tokens": tokens, "targets": tokens}).compile().as_text())
    assert any("f32[16,16384,128]" in r for r in grad), grad


# the parent program's step (chunked scan): 7,979,147,264 B of arguments
# and 3,274,177,024 B of temporaries
HYBRID_ARGS, HYBRID_TEMPS = 7_979_147_264, 3_274_177_024


def test_hybrid_train_step_on_v5e(one_chip, monkeypatch):
    """Granite-4.0-H-Micro's one-chip period at seq 8192: the trainer's step
    (loss and gradient, clip, Adam, donation) runs its NoPE attention layer
    through the flash kernel and its backward (D = 64), and needs no more
    memory than the chunked scan's step."""
    import repro.kernels
    from repro.configs import granite_4_0_h_micro
    from repro.models import init_params
    from repro.optim import Adam
    from repro.train import Trainer, TrainerConfig
    monkeypatch.setattr(repro.kernels, "default_use_pallas", lambda: True)
    cfg = granite_4_0_h_micro.ONE_CHIP
    adam = Adam(lr=1e-3)
    step = Trainer(cfg, {}, adam, iter([]), TrainerConfig(ckpt_dir=None),
                   resume=False)._step_fn

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    compiled = step.lower(params, on_chip(jax.eval_shape(adam.init, params)),
                          {"tokens": tokens, "targets": tokens},
                          None).compile()
    results = _kernel_results(compiled.as_text())
    assert any("f32[8,32768,128]" in r for r in results), results
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes <= HYBRID_ARGS
    assert mem.temp_size_in_bytes <= HYBRID_TEMPS

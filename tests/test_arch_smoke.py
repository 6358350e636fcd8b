"""Per-architecture smoke tests: reduced config, one fwd/train step on CPU.

FULL configs are never allocated here (dry-run only, via ShapeDtypeStruct);
each SMOKE config is the same family at toy width/depth.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import SHAPES, shape_applicable
from repro.models import (
    decode_step,
    init_cache,
    init_params,
    loss,
    prefill,
)
from repro.optim import Adam, apply_updates

KEY = jax.random.PRNGKey(0)


def _smoke_batch(cfg, b=2, s=32):
    kt, ke, ki = jax.random.split(KEY, 3)
    batch = {"targets": jax.random.randint(kt, (b, s), 0, cfg.vocab)}
    if cfg.frontend == "embed_stub":
        batch["embeds"] = jax.random.normal(ke, (b, s, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(ke, (b, s), 0, cfg.vocab)
    if "cross_attn" in cfg.block_pattern:
        batch["image_embeds"] = jax.random.normal(
            ki, (b, cfg.n_patches, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", configs.all_archs())
class TestArchSmoke:
    def test_full_config_matches_assignment(self, arch):
        """The FULL config must carry the exact assigned hyperparameters."""
        cfg = configs.get(arch)
        expected = {
            "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
            "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 0, 151936),
            "llama4_maverick_400b_a17b": (48, 5120, 40, 8, 0, 202048),
            "phi4_mini_3_8b": (32, 3072, 24, 8, 8192, 200064),
            "granite_3_8b": (40, 4096, 32, 8, 12800, 49155),
            "starcoder2_15b": (40, 6144, 48, 4, 24576, 49152),
            "nemotron_4_15b": (32, 6144, 48, 8, 24576, 256000),
            "musicgen_large": (48, 2048, 32, 32, 8192, 2048),
            "llama_3_2_vision_90b": (100, 8192, 64, 8, 28672, 128256),
            "zamba2_1_2b": (38, 2048, 32, 32, 8192, 32000),
            "granite_4_0_h_micro": (40, 2048, 32, 8, 8192, 100352),
        }[arch]
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == expected, (got, expected)
        if arch == "qwen3_moe_235b_a22b":
            assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_d_ff) == \
                (128, 8, 1536)
        if arch == "llama4_maverick_400b_a17b":
            assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_d_ff) == \
                (128, 1, 8192)
        if arch == "zamba2_1_2b":
            assert cfg.ssm_state == 64
        if arch == "granite_4_0_h_micro":
            assert cfg.block_pattern == ("mamba2",) * 5 + ("attn",) + (
                "mamba2",) * 4
            assert (cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_expand,
                    cfg.ssm_conv, cfg.chunk, cfg.d_head) == (
                128, 64, 2, 4, 256, 64)
            assert cfg.ssm_ffn and not cfg.rope
            assert (cfg.embedding_multiplier, cfg.residual_multiplier,
                    cfg.attention_multiplier) == (12.0, 0.22, 1 / 64)

    def test_train_step(self, arch):
        """One forward+backward+update on the reduced config: finite, moving."""
        cfg = configs.get_smoke(arch)
        params = init_params(KEY, cfg)
        batch = _smoke_batch(cfg)
        opt = Adam(lr=1e-3)
        opt_state = opt.init(params)

        @jax.jit
        def step(p, s, b):
            l, g = jax.value_and_grad(lambda pp: loss(pp, cfg, b))(p)
            upd, s = opt.update(g, s, p)
            return apply_updates(p, upd), s, l

        l0 = None
        for i in range(3):
            params, opt_state, l = step(params, opt_state, batch)
            assert np.isfinite(float(l)), (arch, i)
            l0 = float(l) if l0 is None else l0
        assert float(l) < l0 + 1e-3, f"{arch}: loss not decreasing"

    def test_serve_path(self, arch):
        """prefill + one decode token: correct shapes, no NaNs."""
        cfg = configs.get_smoke(arch)
        params = init_params(KEY, cfg)
        b, s = 2, 16
        batch = _smoke_batch(cfg, b=b, s=s)
        batch.pop("targets")
        cache = init_cache(cfg, b, 32)
        h, cache = prefill(params, cfg, batch, cache)
        assert h.shape == (b, s, cfg.d_model)
        step = {"positions": jnp.full((b, 1), s, jnp.int32)}
        if cfg.frontend == "embed_stub":
            step["embeds"] = jax.random.normal(KEY, (b, 1, cfg.d_model))
        else:
            step["tokens"] = jnp.zeros((b, 1), jnp.int32)
        if "cross_attn" in cfg.block_pattern:
            step["image_embeds"] = batch["image_embeds"]
        lg, cache2 = decode_step(params, cfg, step, cache)
        assert lg.shape == (b, 1, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(lg))), arch

    def test_shape_applicability(self, arch):
        """long_500k runs iff the arch is sub-quadratic (SSM/hybrid)."""
        cfg = configs.get(arch)
        skip = shape_applicable(cfg, SHAPES["long_500k"])
        if arch in ("xlstm_350m", "zamba2_1_2b", "granite_4_0_h_micro"):
            assert skip is None
        else:
            assert skip is not None
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(cfg, SHAPES[s]) is None


def test_lgd_trains_the_hybrid_through_a_refresh():
    """Granite-4.0-H's SMOKE through ``train()`` with the LSH pipeline: the
    refresh re-embeds the corpus with ``pooled_features`` through the
    Mamba-2 and NoPE attention layers, and the draws use the new index."""
    from repro.configs import granite_4_0_h_micro
    from repro.launch.train import train
    tr = train(granite_4_0_h_micro.SMOKE, steps=0, batch=2, seq=32,
               corpus=64, lgd=True, refresh_every=2, multiprobe=28)
    losses = tr.run(5)["losses"]
    tr.finalize()
    health = tr.sampler.health_summary()
    assert np.all(np.isfinite(losses))
    assert health["refreshes"] >= 1
    assert health["refresh_failures"] == health["caught_errors"] == 0

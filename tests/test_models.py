"""Model zoo tests: numerics, decode consistency, scan equivalence, MoE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis wheel; use the shim
    from _hypothesis_compat import given, settings, st

from repro.models import (
    ModelConfig,
    decode_step,
    forward,
    init_cache,
    init_params,
    logits,
    loss,
    prefill,
)
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import (
    gla_chunked,
    gla_decode_step,
)

KEY = jax.random.PRNGKey(0)


def tiny(name, **kw):
    base = dict(
        name=name, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=97, chunk=16, loss_chunk=16, dtype="float32",
        rope_theta=10000.0,
    )
    base.update(kw)
    return ModelConfig(**base)


CONFIGS = {
    "dense": tiny("dense"),
    "sqrelu": tiny("sqrelu", act="squared_relu"),
    "gelu": tiny("gelu", act="gelu"),
    "moe": tiny("moe", n_kv_heads=4, moe_experts=8, moe_top_k=2, moe_d_ff=32),
    "mamba": tiny("mamba", n_layers=4, d_ff=0, n_kv_heads=4,
                  block_pattern=("mamba2",), ssm_state=16),
    "xlstm": tiny("xlstm", n_layers=4, d_ff=0, n_kv_heads=4,
                  block_pattern=("mlstm", "slstm")),
    "zamba": tiny("zamba", n_layers=6, n_kv_heads=4,
                  block_pattern=("mamba2", "mamba2", "shared_attn"),
                  ssm_state=16),
    "vision": tiny("vision", n_layers=4,
                   block_pattern=("attn", "cross_attn")),
    "audio": tiny("audio", n_kv_heads=4, frontend="embed_stub"),
    # Granite-4.0-H's layer: Mamba-2 and NoPE attention layers, each with an
    # MLP, and the embedding, residual and score multipliers
    "granite4h": tiny("granite4h", n_layers=6,
                      block_pattern=("mamba2", "mamba2", "attn"),
                      ssm_state=16, ssm_head_dim=16, ssm_ffn=True,
                      rope=False, embedding_multiplier=12.0,
                      residual_multiplier=0.22, attention_multiplier=1 / 64),
}


def make_batch(cfg, b=2, s=32, key=KEY):
    kt, ke, ki = jax.random.split(key, 3)
    batch = {"targets": jax.random.randint(kt, (b, s), 0, cfg.vocab)}
    if cfg.frontend == "embed_stub":
        batch["embeds"] = jax.random.normal(ke, (b, s, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(ke, (b, s), 0, cfg.vocab)
    if "cross_attn" in cfg.block_pattern:
        batch["image_embeds"] = jax.random.normal(ki, (b, 8, cfg.d_model))
    return batch


class TestForward:
    @pytest.mark.parametrize("name", list(CONFIGS))
    def test_loss_finite_and_near_uniform_at_init(self, name):
        cfg = CONFIGS[name]
        params = init_params(KEY, cfg)
        batch = make_batch(cfg)
        l = float(jax.jit(lambda p, b: loss(p, cfg, b))(params, batch))
        assert np.isfinite(l)
        # at random init the LM loss should be near ln(vocab)
        assert abs(l - np.log(cfg.vocab)) < 1.5, l

    @pytest.mark.parametrize("name", ["dense", "mamba", "zamba", "granite4h"])
    def test_scan_equals_unrolled(self, name):
        cfg = CONFIGS[name]
        params = init_params(KEY, cfg)
        batch = make_batch(cfg)
        h_scan = forward(params, cfg.with_(scan_layers=True), batch)
        h_loop = forward(params, cfg.with_(scan_layers=False), batch)
        np.testing.assert_allclose(
            np.asarray(h_scan), np.asarray(h_loop), rtol=2e-4, atol=2e-4)

    def test_remat_matches_no_remat(self):
        cfg = CONFIGS["dense"]
        params = init_params(KEY, cfg)
        batch = make_batch(cfg)
        g1 = jax.grad(lambda p: loss(p, cfg.with_(remat=True), batch))(params)
        g2 = jax.grad(lambda p: loss(p, cfg.with_(remat=False), batch))(params)
        flat1, flat2 = jax.tree.leaves(g1), jax.tree.leaves(g2)
        for a, b in zip(flat1, flat2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_per_block_remat_matches_no_remat_on_the_hybrid(self):
        """Each block of a multi-block pattern is rematerialised on its
        own; its gradients equal the un-rematerialised ones."""
        cfg = CONFIGS["granite4h"]
        params = init_params(KEY, cfg)
        batch = make_batch(cfg)
        g1 = jax.grad(lambda p: loss(p, cfg.with_(remat=True), batch))(params)
        g2 = jax.grad(lambda p: loss(p, cfg.with_(remat=False), batch))(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_grads_nonzero_everywhere(self):
        """No dead parameters: every leaf gets gradient signal."""
        cfg = CONFIGS["zamba"]
        params = init_params(KEY, cfg)
        batch = make_batch(cfg)
        g = jax.grad(lambda p: loss(p, cfg, batch))(params)
        flat = jax.tree_util.tree_flatten_with_path(g)[0]
        dead = [
            "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
            for kp, v in flat if float(jnp.max(jnp.abs(v))) == 0.0
        ]
        assert not dead, f"dead params: {dead}"


class TestDecode:
    @pytest.mark.parametrize("name", ["dense", "mamba", "xlstm", "zamba",
                                      "audio", "vision", "granite4h"])
    def test_decode_matches_forward(self, name):
        """prefill(prompt) then decode(next) == forward(prompt+next) last pos."""
        cfg = CONFIGS[name]
        params = init_params(KEY, cfg)
        b, s = 2, 17
        batch = make_batch(cfg, b=b, s=s)
        full = logits(params, cfg, batch)                 # (B, S, V)

        prompt = {k: (v[:, : s - 1] if v.ndim >= 2 and v.shape[1] == s else v)
                  for k, v in batch.items()}
        cache = init_cache(cfg, b, 32)
        _, cache = prefill(params, cfg, prompt, cache)
        step = {"positions": jnp.full((b, 1), s - 1, jnp.int32)}
        if cfg.frontend == "embed_stub":
            step["embeds"] = batch["embeds"][:, s - 1:s]
        else:
            step["tokens"] = batch["tokens"][:, s - 1:s]
        if "image_embeds" in batch:
            step["image_embeds"] = batch["image_embeds"]
        lg, _ = decode_step(params, cfg, step, cache)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
            rtol=2e-3, atol=2e-3,
        )

    def test_multi_step_decode_consistent(self):
        cfg = CONFIGS["dense"]
        params = init_params(KEY, cfg)
        b, s = 2, 12
        batch = make_batch(cfg, b=b, s=s)
        full = logits(params, cfg, batch)
        prompt = {"tokens": batch["tokens"][:, :8], "targets": None}
        cache = init_cache(cfg, b, 32)
        _, cache = prefill(params, cfg, {"tokens": prompt["tokens"]}, cache)
        for t in range(8, s):
            step = {"tokens": batch["tokens"][:, t:t + 1],
                    "positions": jnp.full((b, 1), t, jnp.int32)}
            lg, cache = decode_step(params, cfg, step, cache)
            np.testing.assert_allclose(
                np.asarray(lg[:, 0]), np.asarray(full[:, t]),
                rtol=2e-3, atol=2e-3,
            )


class TestGLACore:
    @settings(deadline=None, max_examples=15)
    @given(
        s=st.sampled_from([8, 16, 32]),
        chunk=st.sampled_from([4, 8, 16, 32]),
        n=st.sampled_from([4, 8]),
        p=st.sampled_from([4, 8]),
    )
    def test_chunked_equals_naive_recurrence(self, s, chunk, n, p):
        """Property: chunked scan == step-by-step recurrence for any shapes."""
        b, h = 2, 3
        kq, kk, kv, ka = jax.random.split(jax.random.PRNGKey(s * chunk), 4)
        q = jax.random.normal(kq, (b, s, h, n))
        k = jax.random.normal(kk, (b, s, h, n))
        v = jax.random.normal(kv, (b, s, h, p))
        log_a = -jax.nn.softplus(jax.random.normal(ka, (b, s, h)))
        y_chunk, state_chunk = gla_chunked(q, k, v, log_a, chunk)

        state = jnp.zeros((b, h, n, p))
        ys = []
        for t in range(s):
            yt, state = gla_decode_step(
                q[:, t], k[:, t], v[:, t], log_a[:, t], state)
            ys.append(yt)
        y_naive = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(state_chunk), np.asarray(state),
                                   rtol=1e-4, atol=1e-4)


def _mamba2_per_token(p, cfg, x):
    """Mamba-2 as published, one token at a time in float32: a conv window
    of the last W inputs of xBC, the (H, N, P) state update
    S = exp(A dt) S + dt B x^T, y = C S + D x, then the gated RMSNorm."""
    d_inner = cfg.ssm_expand * cfg.d_model
    nh, hd, n, w = d_inner // cfg.ssm_head_dim, cfg.ssm_head_dim, \
        cfg.ssm_state, cfg.ssm_conv
    b, s, _ = x.shape

    def rms(v, scale):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                            + cfg.norm_eps) * scale
    proj = rms(x, p["norm"]["scale"]) @ p["in_proj"]
    z, xbc, dt_raw = (proj[..., :d_inner], proj[..., d_inner:-nh],
                      proj[..., -nh:])
    window = jnp.zeros((b, w, xbc.shape[-1]))
    state = jnp.zeros((b, nh, n, hd))
    a = -jnp.exp(p["a_log"])
    outs = []
    for t in range(s):
        window = jnp.concatenate([window[:, 1:], xbc[:, t:t + 1]], axis=1)
        u = jax.nn.silu(jnp.einsum("bwc,wc->bc", window, p["conv_w"])
                        + p["conv_b"])
        xs = u[:, :d_inner].reshape(b, nh, hd)
        bt, ct = u[:, d_inner:d_inner + n], u[:, d_inner + n:]
        dt = jax.nn.softplus(dt_raw[:, t] + p["dt_bias"])        # (b, nh)
        state = (jnp.exp(a * dt)[..., None, None] * state
                 + dt[..., None, None] * bt[:, None, :, None]
                 * xs[:, :, None, :])
        y = jnp.einsum("bn,bhnp->bhp", ct, state) + p["d_skip"][:, None] * xs
        g = y.reshape(b, d_inner) * jax.nn.silu(z[:, t])
        outs.append(rms(g, p["gate_norm"]["scale"]) @ p["out_proj"])
    return x + cfg.residual_multiplier * jnp.stack(outs, axis=1)


class TestMamba2Block:
    @pytest.mark.parametrize("s, chunk", [(37, 16), (32, 8)])
    def test_block_equals_per_token_recurrence(self, s, chunk):
        """The chunked block (conv as shifted sums, SSD by chunks) against
        the per-token recurrence, at small size in float32."""
        from repro.models.ssm import init_mamba2, mamba2
        cfg = CONFIGS["granite4h"].with_(chunk=chunk)
        p = init_mamba2(jax.random.PRNGKey(3), cfg)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, s, cfg.d_model))
        got, state = mamba2(p, cfg, x)
        want = _mamba2_per_token(p, cfg, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        d_in = cfg.ssm_expand * cfg.d_model
        assert state["conv"].shape == (2, cfg.ssm_conv - 1,
                                       d_in + 2 * cfg.ssm_state)

    def test_init_spreads_the_decays(self):
        """A = exp(a_log) in [1, 16] and dt = softplus(dt_bias) in
        [1e-3, 1e-1], different for every head."""
        from repro.models.ssm import init_mamba2
        cfg = CONFIGS["granite4h"].with_(d_model=512, ssm_head_dim=16)
        p = init_mamba2(jax.random.PRNGKey(5), cfg)
        a = np.exp(np.asarray(p["a_log"]))
        dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert a.shape == dt.shape == (64,)
        assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        assert len(np.unique(a)) == 64

    def test_long_chunks_keep_gradients_finite(self):
        """Fast decays over a 256-step chunk: exp of the unmasked decay
        ratio would overflow; the gradient stays finite."""
        b, s, h, n, p = 1, 256, 2, 4, 4
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(kq, (b, s, 1, n))
        k = jax.random.normal(kk, (b, s, 1, n))
        v = jax.random.normal(kv, (b, s, h, p))
        log_a = jnp.full((b, s, h), -1.6)
        g = jax.grad(lambda v: jnp.sum(gla_chunked(q, k, v, log_a, 256)[0]))(v)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestMoE:
    def test_moe_matches_dense_per_token_at_high_capacity(self):
        """With capacity >= T*k the dispatch must equal exact top-k routing."""
        cfg = tiny("moe_exact", n_kv_heads=4, moe_experts=4, moe_top_k=2,
                   moe_d_ff=16, moe_capacity_factor=8.0)
        p = init_moe(KEY, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        got = moe_ffn(p, cfg, x)

        # naive per-token reference
        from repro.models.layers import rms_norm
        h = rms_norm(p["norm"], x, cfg.norm_eps).reshape(-1, cfg.d_model)
        logits_r = h @ p["router"]
        gates, experts = jax.lax.top_k(logits_r, 2)
        gates = jax.nn.softmax(gates, axis=-1)
        out = jnp.zeros_like(h)
        for t in range(h.shape[0]):
            acc = jnp.zeros((cfg.d_model,))
            for j in range(2):
                e = int(experts[t, j])
                ge = jax.nn.silu(h[t] @ p["experts_gate"][e]) * (
                    h[t] @ p["experts_up"][e])
                acc = acc + gates[t, j] * (ge @ p["experts_down"][e])
            out = out.at[t].set(acc)
        want = x + out.reshape(x.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_moe_capacity_drops_are_bounded(self):
        """With capacity_factor 1.0 some tokens drop but output stays finite
        and the residual path preserves them."""
        cfg = tiny("moe_drop", n_kv_heads=4, moe_experts=4, moe_top_k=1,
                   moe_d_ff=16, moe_capacity_factor=1.0)
        p = init_moe(KEY, cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
        y = moe_ffn(p, cfg, x)
        assert bool(jnp.all(jnp.isfinite(y)))
        assert y.shape == x.shape


class TestChunkedAttention:
    @pytest.mark.parametrize("s,bq", [(32, 8), (33, 8), (64, 64), (17, 32)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, s, bq, causal):
        from repro.models.attention_xla import chunked_gqa_attention
        from repro.kernels.flash_attention import gqa_attention
        b, hq, hkv, d = 2, 8, 2, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(kq, (b, s, hq, d))
        k = jax.random.normal(kk, (b, s, hkv, d))
        v = jax.random.normal(kv, (b, s, hkv, d))
        got = chunked_gqa_attention(q, k, v, causal=causal, block_q=bq)
        want = gqa_attention(q, k, v, causal=causal, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_ref(self):
        from repro.models.attention_xla import chunked_gqa_attention
        from repro.kernels.flash_attention import gqa_attention
        b, s, hq, hkv, d = 1, 32, 4, 2, 8
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(kq, (b, s, hq, d))
        k = jax.random.normal(kk, (b, s, hkv, d))
        v = jax.random.normal(kv, (b, s, hkv, d))
        f1 = lambda q, k, v: jnp.sum(
            chunked_gqa_attention(q, k, v, causal=True, block_q=8) ** 2)
        f2 = lambda q, k, v: jnp.sum(
            gqa_attention(q, k, v, causal=True, use_pallas=False) ** 2)
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)


class TestEmbedAttention:
    """``forward`` (under the loss and its gradient, and ``pooled_features``,
    the LGD refresh embed) runs self-attention through the flash kernel
    where ``attention_path`` says so: TPU, params on one device, kernel
    blocks dividing the sequence."""

    @pytest.fixture
    def tpu_path(self, monkeypatch):
        """The kernel path on this CPU host: the backend rule reads TPU and
        the kernel runs in interpret mode."""
        import repro.kernels
        from repro.kernels.flash_attention import (
            flash_attention_pallas, gqa_attention, ops)

        def interpreted(*args, **kw):
            return flash_attention_pallas(*args, **{**kw, "interpret": True})
        monkeypatch.setattr(repro.kernels, "default_use_pallas",
                            lambda: True)
        monkeypatch.setattr(ops, "flash_attention_pallas", interpreted)
        gqa_attention.clear_cache()
        yield
        gqa_attention.clear_cache()

    def test_cpu_takes_the_chunked_path(self):
        from repro.models.lm import attention_path
        params = init_params(KEY, tiny("embed_cpu"))
        assert attention_path(params, 128) == "chunked"

    def test_rule(self, tpu_path):
        from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec
        from repro.models.lm import attention_path
        cfg = tiny("embed_rule")
        params = init_params(KEY, cfg)
        assert attention_path(params, 128) == "flash"
        assert attention_path(params, 4096) == "flash"
        assert attention_path(params, 100) == "chunked"   # no block fits
        meshed = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(
                AbstractMesh((4,), ("data",)), PartitionSpec())), params)
        assert attention_path(meshed, 128) == "chunked"   # four devices

    def test_pooled_features_flash_matches_chunked(self, tpu_path):
        """At smoke width in bf16, the kernel's features equal the chunked
        scan's within bf16 rounding, and the loss takes the kernel too."""
        import repro.kernels
        from repro.configs import granite_3_8b
        from repro.models import pooled_features
        cfg = granite_3_8b.SMOKE.with_(dtype="bfloat16")
        params = init_params(KEY, cfg)
        batch = make_batch(cfg, b=2, s=128)
        got = jax.jit(lambda p, b: pooled_features(p, cfg, b))(params, batch)
        loss_jaxpr = str(jax.make_jaxpr(lambda p, b: loss(p, cfg, b))(
            params, batch))
        repro.kernels.default_use_pallas = lambda: False
        want = jax.jit(lambda p, b: pooled_features(p, cfg, b))(params, batch)
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        gap = np.linalg.norm(got - want, axis=1) / np.linalg.norm(
            want, axis=1)
        assert gap.max() < 2e-2, gap
        assert not np.array_equal(got, want)    # the kernel ran
        assert "pallas_call" in loss_jaxpr

    @pytest.mark.parametrize("arch, dtype, tol", [
        ("granite_3_8b", "float32", 1e-4),
        ("granite_3_8b", "bfloat16", 3e-2),
        # NoPE attention inside the hybrid's ordered remat
        ("granite_4_0_h_micro", "float32", 1e-4),
    ])
    def test_loss_and_grad_flash_match_chunked(self, tpu_path, arch, dtype,
                                               tol):
        """The loss and its gradient through the kernel's backward equal
        the chunked scan's at smoke width: to f32 round-off, and in bf16
        within bf16 rounding (each path then sits about 1.5% from the f32
        gradient)."""
        import importlib
        import repro.kernels
        cfg = importlib.import_module(f"repro.configs.{arch}").SMOKE.with_(
            dtype=dtype)
        params = init_params(KEY, cfg)
        batch = make_batch(cfg, b=2, s=256)
        fn = jax.value_and_grad(lambda p, b: loss(p, cfg, b))
        assert "pallas_call" in str(jax.make_jaxpr(fn)(params, batch))
        got = jax.jit(fn)(params, batch)
        repro.kernels.default_use_pallas = lambda: False
        want = jax.jit(fn)(params, batch)
        assert abs(float(got[0]) - float(want[0])) <= tol * abs(
            float(want[0]))
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                                jax.tree.leaves(want[1])):
            g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
            gap = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-12)
            assert gap < tol, (jax.tree_util.keystr(path), gap)

    def test_train_dispatch_names_the_attention_path(self, monkeypatch):
        """The trainer's ``train/dispatch`` span carries ``attn=``, the
        path of the model's loss: the chunked scan on this CPU host."""
        from repro.optim import Adam
        from repro.spans import Spans
        from repro.train import Trainer, TrainerConfig
        seen = []
        real = Spans.__call__

        def record(self, name, **meta):
            seen.append((name, meta))
            return real(self, name, **meta)
        monkeypatch.setattr(Spans, "__call__", record)
        cfg = tiny("dispatch_attn")
        batches = iter([make_batch(cfg, b=2, s=16)] * 2)
        Trainer(cfg, init_params(KEY, cfg), Adam(lr=1e-3), batches,
                TrainerConfig(ckpt_dir=None)).run(2)
        attn = [m.get("attn") for n, m in seen if n == "train/dispatch"]
        assert attn == ["chunked", "chunked"]

"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracle.

Shape/dtype sweeps as required: each kernel is exercised across block
boundaries, GQA group sizes, and bf16/f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (
    attention_ref,
    decode_ref,
    flash_attention_pallas,
    flash_decode_pallas,
    gqa_attention,
    gqa_decode,
)
from repro.kernels.simhash import simhash_codes, simhash_codes_ref

KEY = jax.random.PRNGKey(0)


class TestSimhashKernel:
    @pytest.mark.parametrize("n,d,k,l", [
        (256, 64, 5, 8),      # exact block fit
        (300, 91, 5, 100),    # paper's YearMSD-like dims, padding needed
        (64, 530, 7, 10),     # paper's BERT params, UJIIndoorLoc dims
        (8, 16, 1, 1),        # degenerate
        (512, 128, 32, 4),    # max K
    ])
    def test_matches_ref(self, n, d, k, l):
        kx, kw = jax.random.split(jax.random.fold_in(KEY, n * d))
        x = jax.random.normal(kx, (n, d))
        w = jax.random.normal(kw, (d, l * k))
        got = simhash_codes(x, w, k=k, l=l, use_pallas=True, interpret=True)
        want = simhash_codes_ref(x, w, k=k, l=l)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bf16_input(self):
        kx, kw = jax.random.split(KEY)
        x = jax.random.normal(kx, (128, 64), jnp.bfloat16)
        w = jax.random.normal(kw, (64, 40))
        got = simhash_codes(x, w, k=5, l=8, use_pallas=True, interpret=True)
        want = simhash_codes_ref(x, w, k=5, l=8)
        # bf16 rounding can flip signs on near-zero projections
        agree = np.mean(np.asarray(got) == np.asarray(want))
        assert agree > 0.97, agree

    def test_matches_core_compute_codes(self):
        """The kernel must agree with repro.core.simhash.compute_codes."""
        from repro.core.simhash import LSHParams, compute_codes, make_projections
        p = LSHParams(k=5, l=10, dim=33, family="dense")
        proj = make_projections(KEY, p)
        x = jax.random.normal(jax.random.PRNGKey(1), (100, 33))
        want = compute_codes(x, proj, k=5, l=10)
        got = simhash_codes(x, proj, k=5, l=10, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _qkv(key, b, hkv, g, s, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hkv, g, s, d), dtype)
    k = jax.random.normal(kk, (b, hkv, s, d), dtype)
    v = jax.random.normal(kv, (b, hkv, s, d), dtype)
    return q, k, v


def _pallas_outputs(jaxpr):
    """(shape, dtype) of each output of every ``pallas_call`` in ``jaxpr``
    and the jaxprs nested in it, in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([(v.aval.shape, v.aval.dtype) for v in eqn.outvars])
            continue
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                out += _pallas_outputs(sub)
    return out


class TestFlashAttention:
    @pytest.mark.parametrize("b,hkv,g,s,d,bq,bk", [
        (1, 1, 1, 128, 64, 64, 64),
        (2, 2, 4, 128, 64, 64, 64),     # GQA group 4
        (1, 1, 2, 256, 128, 128, 64),   # uneven q/k blocks
        (1, 2, 1, 64, 32, 64, 32),      # single q block
        # GQA 4, bq != bk both ways: the K/V index map is clamped past
        # the diagonal and only diagonal-crossing blocks are masked
        (2, 2, 4, 512, 64, 128, 256),
        (2, 2, 4, 512, 64, 256, 128),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, b, hkv, g, s, d, bq, bk, causal):
        q, k, v = _qkv(jax.random.fold_in(KEY, s * d + g), b, hkv, g, s, d)
        got = flash_attention_pallas(
            q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
        )
        want = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_bf16(self):
        q, k, v = _qkv(KEY, 1, 2, 2, 128, 64, jnp.bfloat16)
        got = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                     block_k=64, interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
    def test_bf16_uneven_blocks(self, bq, bk):
        """bf16 inputs: P is cast to bf16 for the P.V matmul, with f32
        statistics and accumulation."""
        q, k, v = _qkv(KEY, 1, 2, 4, 512, 128, jnp.bfloat16)
        got = flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                     block_k=bk, interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    @pytest.mark.parametrize("dtype, tol", [
        (jnp.float32, 1e-5),
        (jnp.bfloat16, 2e-2),    # the gradients come out in bf16
    ])
    @pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("g", [1, 4])
    def test_gradients_match_ref(self, g, d, bq, bk, dtype, tol):
        """dQ, dK and dV of the custom-VJP kernel (P recomputed from the
        saved log-sum-exp) equal the oracle's autodiff gradients, causal,
        at S = 512 across blocks that cross the diagonal both ways."""
        q, k, v = _qkv(jax.random.fold_in(KEY, g * d), 1, 2, g, 512, d, dtype)
        w = jax.random.normal(jax.random.fold_in(KEY, 1), q.shape)

        def through(attend):
            return lambda q, k, v: jnp.sum(
                attend(q, k, v).astype(jnp.float32) * w)
        got = jax.grad(through(lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(through(lambda q, k, v: attention_ref(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", got, want):
            assert a.dtype == dtype, name
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() <= tol * np.abs(b).max(), name

    def test_only_the_differentiated_forward_saves_the_lse(self):
        """The primal call runs the forward kernel alone, with one output;
        the gradient's forward adds the f32 log-sum-exp, and the dK/dV and
        dQ kernels follow."""
        q, k, v = _qkv(KEY, 1, 2, 4, 256, 64)

        def attend(q, k, v):
            return flash_attention_pallas(q, k, v, block_q=128,
                                          block_k=128, interpret=True)
        primal = _pallas_outputs(jax.make_jaxpr(attend)(q, k, v).jaxpr)
        assert primal == [[((2, 1024, 64), jnp.float32)]]
        vjp = jax.make_jaxpr(lambda q, k, v: jax.vjp(attend, q, k, v)[1](q))
        assert _pallas_outputs(vjp(q, k, v).jaxpr) == [
            [((2, 1024, 64), jnp.float32), ((2, 1024, 128), jnp.float32)],
            [((2, 256, 64), jnp.float32)] * 2,        # dK, dV
            [((2, 1024, 64), jnp.float32)],           # dQ
        ]

    def test_default_blocks(self):
        from repro.kernels.flash_attention.kernel import default_blocks
        assert default_blocks(4096) == (512, 512)
        assert default_blocks(1024) == (512, 512)
        assert default_blocks(384) == (128, 128)
        assert default_blocks(100) is None

    def test_gqa_wrapper_model_layout(self):
        b, s, hq, hkv, d = 2, 128, 8, 2, 64
        kq, kk, kv = jax.random.split(KEY, 3)
        q = jax.random.normal(kq, (b, s, hq, d))
        k = jax.random.normal(kk, (b, s, hkv, d))
        v = jax.random.normal(kv, (b, s, hkv, d))
        got = gqa_attention(q, k, v, causal=True, use_pallas=True,
                            interpret=True, block_q=64, block_k=64)
        want = gqa_attention(q, k, v, causal=True, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestFlashDecode:
    @pytest.mark.parametrize("b,hkv,g,s,d,bk", [
        (2, 2, 1, 512, 64, 256),
        (1, 4, 4, 1024, 128, 512),
        (3, 1, 8, 256, 64, 128),
    ])
    def test_matches_ref(self, b, hkv, g, s, d, bk):
        kq, kk, kv, kl = jax.random.split(jax.random.fold_in(KEY, s + d), 4)
        q = jax.random.normal(kq, (b, hkv, g, d))
        k = jax.random.normal(kk, (b, hkv, s, d))
        v = jax.random.normal(kv, (b, hkv, s, d))
        kv_len = jax.random.randint(kl, (b,), 1, s + 1)
        got = flash_decode_pallas(q, k, v, kv_len, block_k=bk, interpret=True)
        want = decode_ref(q, k, v, kv_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gqa_decode_wrapper(self):
        b, s, hq, hkv, d = 2, 256, 8, 4, 64
        kq, kk, kv = jax.random.split(KEY, 3)
        q = jax.random.normal(kq, (b, 1, hq, d))
        kc = jax.random.normal(kk, (b, s, hkv, d))
        vc = jax.random.normal(kv, (b, s, hkv, d))
        kv_len = jnp.array([s, s // 2], jnp.int32)
        got = gqa_decode(q, kc, vc, kv_len, use_pallas=True, interpret=True,
                         block_k=128)
        want = gqa_decode(q, kc, vc, kv_len, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_agrees_with_full_attention_last_token(self):
        """Decoding token s against cache[0:s] == causal attention row s."""
        b, hkv, g, s, d = 1, 2, 2, 128, 64
        q5, k5, v5 = _qkv(KEY, b, hkv, g, s, d)
        full = attention_ref(q5, k5, v5, causal=True)
        got = flash_decode_pallas(
            q5[:, :, :, -1], k5, v5, jnp.array([s]), block_k=64,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(full[:, :, :, -1]), rtol=1e-5,
            atol=1e-5,
        )

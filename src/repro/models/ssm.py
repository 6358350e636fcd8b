"""Sub-quadratic sequence mixers: Mamba-2 (SSD), xLSTM mLSTM/sLSTM.

Mamba-2 and mLSTM share one *chunked gated linear-attention* core:

    S_t = a_t * S_{t-1} + k_t^T v_t          (per-head matrix state, PxN)
    y_t = q_t S_t   (+ normaliser for mLSTM)

computed chunk-parallel (FlashLinearAttention schedule): within a chunk
the contribution is a small causal "attention" matmul weighted by decay
ratios; across chunks a lax.scan carries the (P, N) state.  This is the
TPU-native adaptation — all chunk work is MXU matmuls, the sequential
dependency is only over S/chunk steps.

sLSTM keeps a per-channel scalar state and is inherently sequential;
it runs as a lax.scan over time (xLSTM uses few sLSTM blocks).

Decode: every mixer exposes a single-token state-update path with O(1)
cost per token — the reason these archs run the long_500k shape.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical
from .config import ModelConfig
from .layers import init_rmsnorm, residual, rms_norm


# ---------------------------------------------------------------------------
# chunked gated linear attention core
# ---------------------------------------------------------------------------

def gla_chunked(
    q: jax.Array,        # (B, S, H or 1, N)  query / C in mamba2
    k: jax.Array,        # (B, S, H or 1, N)  key   / B in mamba2
    v: jax.Array,        # (B, S, H, P)  value / x in mamba2
    log_a: jax.Array,    # (B, S, H)     per-step log decay (<= 0)
    chunk: int,
    state0: Optional[jax.Array] = None,   # (B, H, N, P)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), state (B,H,N,P)).

    ``q`` and ``k`` with one head are shared by all H heads of ``v``
    (Mamba-2's single B/C group): their (c, c) products are computed once
    per chunk, not once per head."""
    b, s, hk, n = q.shape
    h, p = v.shape[2], v.shape[-1]
    c = min(chunk, s)
    s_orig = s
    if s % c != 0:
        # pad with zero-k/v and zero log-decay: state passes through pads
        pad = c - s % c
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // c

    qc = q.reshape(b, nc, c, hk, n).transpose(1, 0, 3, 2, 4)  # (nc,B,hk,c,N)
    kc = k.reshape(b, nc, c, hk, n).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nc, c, h, p).transpose(1, 0, 3, 2, 4)  # (nc,B,H,c,P)
    la = log_a.reshape(b, nc, c, h).transpose(1, 0, 3, 2)    # (nc,B,H,c)

    cum = jnp.cumsum(la, axis=-1)                            # (nc,B,H,c)
    total = cum[..., -1:]

    if state0 is None:
        state0 = jnp.zeros((b, h, n, p), jnp.float32)
    mask = jnp.tril(jnp.ones((c, c), bool))

    def step(state, xs):
        qi, ki, vi, cumi, toti = xs
        # decay from chunk start to position t (inclusive of a_t)
        d_q = jnp.exp(cumi)                                  # (B,H,c)
        # decay from position t (exclusive) to chunk end
        d_k = jnp.exp(toti - cumi)                           # (B,H,c)
        # intra-chunk causal attention with decay ratio exp(cum_i - cum_j),
        # its exponent masked before exp: above the diagonal it is > 0 and
        # may overflow, and inf there would make the gradient nan
        att = jnp.einsum("bhin,bhjn->bhij", qi, ki)          # (B,hk,c,c)
        ratio = jnp.exp(jnp.where(
            mask, cumi[..., :, None] - cumi[..., None, :], -jnp.inf))
        att = att * ratio                                    # (B,H,c,c)
        y_intra = jnp.einsum("bhij,bhjp->bhip", att, vi)
        # inter-chunk: carried state
        y_state = jnp.einsum("bhin,bhnp->bhip", qi * d_q[..., None], state)
        # state update
        k_dec = ki * d_k[..., None]                          # (B,H,c,N)
        state_new = state * jnp.exp(toti)[..., None] + jnp.einsum(
            "bhcn,bhcp->bhnp", k_dec, vi)
        return state_new, y_intra + y_state

    qf = qc.astype(jnp.float32)
    kf = kc.astype(jnp.float32)
    vf = vc.astype(jnp.float32)
    # each chunk's (c, c) products are recomputed in the backward pass, not
    # kept for all S / c chunks at once
    state, ys = jax.lax.scan(jax.checkpoint(step), state0,
                             (qf, kf, vf, cum, total))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(v.dtype), state


def gla_decode_step(
    q: jax.Array,      # (B, H, N)
    k: jax.Array,      # (B, H, N)
    v: jax.Array,      # (B, H, P)
    log_a: jax.Array,  # (B, H)
    state: jax.Array,  # (B, H, N, P)
) -> Tuple[jax.Array, jax.Array]:
    a = jnp.exp(log_a)[..., None, None].astype(jnp.float32)
    state = state * a + jnp.einsum(
        "bhn,bhp->bhnp", k.astype(jnp.float32), v.astype(jnp.float32))
    y = jnp.einsum("bhn,bhnp->bhp", q.astype(jnp.float32), state)
    return y.astype(v.dtype), state


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------

def _mamba_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state


def init_mamba2(key, cfg: ModelConfig):
    """Mamba-2's initialisation: per-head decay rates A ~ U[1, 16], steps
    dt ~ exp U[log 1e-3, log 1e-1] held as ``dt_bias = softplus^-1(dt)``,
    conv taps and bias ~ U(+-1/sqrt(W)) (a depthwise Conv1d's default).

    The conv taps stay float32 with the block's other small parameters:
    at |w| ~ 0.25 a bfloat16 tap rounds away updates under 1e-3, which at
    the usual learning rates is most of them."""
    d = cfg.d_model
    d_inner, nh, n = _mamba_dims(cfg)
    conv_dim = d_inner + 2 * n
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kw, kb = jax.random.split(k3)
    ka, kt = jax.random.split(k4)
    dt = jnp.dtype(cfg.dtype)
    s = d ** -0.5
    bound = cfg.ssm_conv ** -0.5
    step = jnp.exp(jax.random.uniform(
        kt, (nh,), minval=math.log(1e-3), maxval=math.log(1e-1)))
    # in_proj emits [z (d_inner), xBC (d_inner + 2N), dt (nh)]
    return {
        "norm": init_rmsnorm(d),
        "in_proj": (jax.random.normal(k1, (d, d_inner + conv_dim + nh))
                    * s).astype(dt),
        "conv_w": jax.random.uniform(kw, (cfg.ssm_conv, conv_dim),
                                     minval=-bound, maxval=bound),
        "conv_b": jax.random.uniform(kb, (conv_dim,), minval=-bound,
                                     maxval=bound),
        "a_log": jnp.log(jax.random.uniform(ka, (nh,), minval=1.0,
                                            maxval=16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "gate_norm": init_rmsnorm(d_inner),
        "out_proj": (jax.random.normal(k2, (d_inner, d))
                     * d_inner ** -0.5).astype(dt),
    }


def _causal_conv(p, xbc, window):
    """Depthwise causal conv over time, then SiLU.  ``window`` holds the
    W - 1 input rows before ``xbc`` (zeros at a sequence start); returns
    (activations, the last W - 1 input rows)."""
    s = xbc.shape[1]
    xp = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    taps = p["conv_w"]
    acc = p["conv_b"] + sum(xp[:, i:i + s].astype(jnp.float32) * taps[i]
                            for i in range(taps.shape[0]))
    return jax.nn.silu(acc).astype(xbc.dtype), xp[:, s:]


def _mamba_mix(p, cfg: ModelConfig, x, state, ssd):
    """The Mamba-2 mixer around its state-space core ``ssd``: pre-norm,
    in_proj, causal conv over xBC, dt = softplus(dt + dt_bias), the SSD,
    the D skip, y = RMSNorm(y * silu(z)) * w over d_inner, and out_proj.

    ``ssd(C, B, x * dt, A * dt, ssm_state)`` -> (y, ssm_state), with C and
    B as one shared head (B, S, 1, N).  Returns (out, new state)."""
    d_inner, nh, n = _mamba_dims(cfg)
    b_, s_ = x.shape[:2]
    with jax.named_scope("mamba2"):
        h = rms_norm(p["norm"], x, cfg.norm_eps)
        proj = jnp.einsum("bsd,de->bse", h, p["in_proj"])
        proj = logical(proj, "batch", None, "ff")
        z, xbc, dt_raw = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n],
                                   axis=-1)
        with jax.named_scope("conv"):
            xbc, window = _causal_conv(p, xbc, state["conv"])
        with jax.named_scope("ssd"):
            xs, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
            xs = xs.reshape(b_, s_, nh, cfg.ssm_head_dim).astype(jnp.float32)
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
            log_a = -jnp.exp(p["a_log"]) * dt                # (B,S,nh) <= 0
            y, ssm_state = ssd(cmat[:, :, None], bmat[:, :, None],
                               xs * dt[..., None], log_a, state["ssm"])
            y = y + xs * p["d_skip"][:, None]
        with jax.named_scope("gate_norm"):
            y = y.reshape(b_, s_, d_inner) * jax.nn.silu(
                z.astype(jnp.float32))
            y = rms_norm(p["gate_norm"], y, cfg.norm_eps).astype(x.dtype)
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
        out = residual(cfg, x, logical(out, "batch", None, None))
    return out, {"ssm": ssm_state, "conv": window}


def mamba2(p, cfg: ModelConfig, x: jax.Array, state=None):
    """Returns (out, new_state); state: ``init_mamba2_state``'s, or None
    at a sequence start."""
    if state is None:
        state = init_mamba2_state(cfg, x.shape[0])
    return _mamba_mix(p, cfg, x, state,
                      lambda q, k, v, la, st: gla_chunked(q, k, v, la,
                                                          cfg.chunk, st))


def mamba2_decode(p, cfg: ModelConfig, x: jax.Array, state):
    """x: (B, 1, d). O(1) per-token update of the SSM state and the conv
    window."""
    def one_step(q, k, v, la, st):
        nh = v.shape[2]
        q, k = (jnp.broadcast_to(a[:, 0], (a.shape[0], nh, a.shape[-1]))
                for a in (q, k))
        y, st = gla_decode_step(q, k, v[:, 0], la[:, 0], st)
        return y[:, None], st
    return _mamba_mix(p, cfg, x, state, one_step)


def init_mamba2_state(cfg: ModelConfig, batch: int):
    """The SSM state (B, H, N, P) f32 and the conv window: the last W - 1
    rows of xBC (B, W - 1, d_inner + 2N)."""
    d_inner, nh, n = _mamba_dims(cfg)
    return {
        "ssm": jnp.zeros((batch, nh, n, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_inner + 2 * n),
                          jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# xLSTM mLSTM block (matrix memory + exponential gating)
# ---------------------------------------------------------------------------

def init_mlstm(key, cfg: ModelConfig):
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    s = d ** -0.5
    # qkv + input/forget gate pre-activations per head
    return {
        "norm": init_rmsnorm(d),
        "qkv_proj": (jax.random.normal(k1, (d, 3 * d)) * s).astype(dt),
        "gate_proj": (jax.random.normal(k2, (d, 2 * nh)) * s).astype(dt),
        "gate_bias": jnp.concatenate(
            [jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]).astype(jnp.float32),
        "out_proj": (jax.random.normal(k3, (d, d)) * s).astype(dt),
    }


def _mlstm_project(p, cfg, x):
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    b, s, _ = x.shape
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    qkv = jnp.einsum("bsd,de->bse", h, p["qkv_proj"])
    qkv = logical(qkv, "batch", None, "ff")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, nh, dh) * dh ** -0.5
    k = k.reshape(b, s, nh, dh) * dh ** -0.5
    v = v.reshape(b, s, nh, dh)
    gates = jnp.einsum("bsd,de->bse", h, p["gate_proj"]).astype(jnp.float32)
    gates = gates + p["gate_bias"][None, None, :]
    i_gate, f_gate = jnp.split(gates, 2, axis=-1)           # (B,S,nh)
    log_f = jax.nn.log_sigmoid(f_gate)                      # <= 0
    i_scale = jnp.exp(jnp.minimum(i_gate, 0.0))             # stabilised exp
    return q, k * i_scale[..., None].astype(k.dtype), v, log_f


def mlstm(p, cfg: ModelConfig, x: jax.Array,
          state: Optional[jax.Array] = None):
    """Returns (out, new_state); state holds (C, n) stacked: (B,H,dh+1,dh)."""
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    q, k, v, log_f = _mlstm_project(p, cfg, x)
    # normaliser: run the same recurrence with v=1 (appended column)
    v_ext = jnp.concatenate(
        [v, jnp.ones(v.shape[:-1] + (1,), v.dtype)], axis=-1)
    y_ext, new_state = gla_chunked(
        q, k, v_ext, log_f, cfg.chunk, state)
    y, n = y_ext[..., :dh], y_ext[..., dh:]
    y = y / jnp.maximum(jnp.abs(n), 1.0)
    y = y.reshape(x.shape[0], x.shape[1], d)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    return residual(cfg, x, logical(out, "batch", None, None)), new_state


def mlstm_decode(p, cfg: ModelConfig, x: jax.Array, state: jax.Array):
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    q, k, v, log_f = _mlstm_project(p, cfg, x)
    v_ext = jnp.concatenate(
        [v, jnp.ones(v.shape[:-1] + (1,), v.dtype)], axis=-1)
    y_ext, new_state = gla_decode_step(
        q[:, 0], k[:, 0], v_ext[:, 0], log_f[:, 0], state)
    y, n = y_ext[..., :dh], y_ext[..., dh:]
    y = (y / jnp.maximum(jnp.abs(n), 1.0)).reshape(x.shape[0], 1, d)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    return residual(cfg, x, logical(out, "batch", None, None)), new_state


def init_mlstm_state(cfg: ModelConfig, batch: int):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    return jnp.zeros((batch, nh, dh, dh + 1), jnp.float32)


# ---------------------------------------------------------------------------
# xLSTM sLSTM block (scalar memory, sequential scan)
# ---------------------------------------------------------------------------

def init_slstm(key, cfg: ModelConfig):
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    s = d ** -0.5
    # fused projections for z (cell input), i, f, o gates
    return {
        "norm": init_rmsnorm(d),
        "in_proj": (jax.random.normal(k1, (d, 4 * d)) * s).astype(dt),
        "out_proj": (jax.random.normal(k2, (d, d)) * s).astype(dt),
    }


def _slstm_scan(zi, ii, fi, oi, carry0):
    """Stabilised sLSTM recurrence over time — PARALLEL form.

    With input-only gates (this implementation projects i/f/o/z from x,
    no hidden-to-hidden recurrence), the stabiliser is a max-plus scan
    and the cell/normaliser updates are first-order linear recurrences —
    all three are ASSOCIATIVE, so the whole layer runs as
    jax.lax.associative_scan in O(log S) depth instead of S sequential
    steps.  TPU win measured in EXPERIMENTS.md §Perf (xlstm train cell:
    the 4096-step while loop was the dominant HBM-traffic term).

    Inputs: (B, S, d) f32; carry0 = (c0, n0, m0) each (B, d).
    """
    c0, n0, m0 = carry0
    log_f = jax.nn.log_sigmoid(fi)                       # (B, S, d)

    # 1) stabiliser: m_t = max(log_f_t + m_{t-1}, i_t)  — max-plus scan
    #    represented as pairs (a, b): m_t = max(a + m_{t-1}, b)
    #    composition: (a2,b2)∘(a1,b1) = (a1+a2, max(b1+a2, b2))
    def mp_op(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 + a2, jnp.maximum(b1 + a2, b2)

    a_all, b_all = jax.lax.associative_scan(
        mp_op, (log_f, ii), axis=1)
    m = jnp.maximum(a_all + m0[:, None, :], b_all)       # (B, S, d)

    m_prev = jnp.concatenate([m0[:, None, :], m[:, :-1]], axis=1)
    i_p = jnp.exp(ii - m)
    f_p = jnp.exp(log_f + m_prev - m)

    # 2) linear recurrences x_t = f'_t x_{t-1} + u_t  (for c and n)
    def lin_op(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    def lin_scan(u, x0):
        aa, bb = jax.lax.associative_scan(lin_op, (f_p, u), axis=1)
        return aa * x0[:, None, :] + bb

    c = lin_scan(i_p * jnp.tanh(zi), c0)
    n = lin_scan(i_p, n0)
    h = jax.nn.sigmoid(oi) * c / jnp.maximum(n, 1.0)
    return h, (c[:, -1], n[:, -1], m[:, -1])


def slstm(p, cfg: ModelConfig, x: jax.Array, state=None):
    """state: (c, n, m) each (B, d) f32."""
    b, s, d = x.shape
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    proj = jnp.einsum("bsd,de->bse", h, p["in_proj"]).astype(jnp.float32)
    z, i, f, o = jnp.split(proj, 4, axis=-1)
    if state is None:
        state = init_slstm_state(cfg, b)
    hs, new_state = _slstm_scan(z, i, f, o, state)
    out = jnp.einsum("bsd,de->bse", hs.astype(x.dtype), p["out_proj"])
    return residual(cfg, x, logical(out, "batch", None, None)), new_state


def init_slstm_state(cfg: ModelConfig, batch: int):
    zeros = jnp.zeros((batch, cfg.d_model), jnp.float32)
    return (zeros, zeros, zeros)

"""The plain reference the benchmark's ``correct`` compares against.

Float32 ``jax.numpy`` at the configuration's published widths.  The forward
pass up to the final hidden state is the architecture's own
(``hidden`` and ``constants`` of ``chipbench/arch/<model_type>.py``, each
function here takes that module as ``arch``); this file holds what every
architecture shares: the final RMSNorm and untied head, and the training
recipe (token-mean cross-entropy with importance weights, global-norm
clipping, Adam under a linear-warm-up cosine schedule).  It imports nothing
of the program.  The head runs in blocks of positions, so a 4096-token step
fits one chip beside nothing else.

``mm`` carries every matrix product.  ``precision`` names what its
operands, in the forward and the backward pass, are rounded to:
``"float32"`` (the reference, at full f32 precision), or a lower one for
the control that stands in the program's place: ``"bfloat16"``, or
``"float8"`` (per-tensor scaled e4m3).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_FP8_MAX = 448.0
HEAD_BLOCK = 256


def lower(x, precision):
    """``x`` as the matrix unit would see it at ``precision`` (f32 values)."""
    x = x.astype(F32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
        return q * scale
    raise ValueError(precision)


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _mm_lowered(spec, a, b, precision):
    return _einsum(spec, lower(a, precision), lower(b, precision))


def _mm_fwd(spec, a, b, precision):
    a, b = lower(a, precision), lower(b, precision)
    return _einsum(spec, a, b), (a, b)


def _mm_bwd(spec, precision, res, g):
    # the backward products take lowered operands too
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(lower(g, precision))


_mm_lowered.defvjp(_mm_fwd, _mm_bwd)


def mm(spec, a, b, precision):
    """A matrix product whose operands, forward and backward, are rounded
    to ``precision``; accumulation is float32."""
    if precision == "float32":
        return _einsum(spec, a.astype(F32), b.astype(F32))
    return _mm_lowered(spec, a, b, precision)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def loss(arch, params, batch, c, precision):
    """Token-mean cross-entropy, each row scaled by its importance weight."""
    h = arch.hidden(params, batch["tokens"], c, precision)
    eg = params["embed_group"]
    h = rms(h, eg["final_norm"]["scale"], c["eps"])
    b, s, d = h.shape
    blk = min(HEAD_BLOCK, s)
    hb = h.reshape(b, s // blk, blk, d).transpose(1, 0, 2, 3)
    tb = batch["targets"].reshape(b, s // blk, blk).transpose(1, 0, 2)
    head = eg["lm_head"].astype(F32)

    @jax.checkpoint
    def one(args):
        hx, tx = args
        logits = mm("bsd,dv->bsv", hx, head, precision)
        gold = jnp.take_along_axis(logits, tx[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold, axis=1)  # (B,)

    per_row = jnp.sum(jax.lax.map(one, (hb, tb)), axis=0)           # (B,)
    w = batch.get("loss_weights")
    if w is not None:
        per_row = per_row * w.astype(F32)
    return jnp.sum(per_row) / (b * s)


def pooled(arch, params, tokens, c, precision):
    """Mean over positions of the final hidden state: one row's feature."""
    return jnp.mean(arch.hidden(params, tokens, c, precision), axis=1)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def pooled_jit(arch, params, tokens, c, precision):
    return pooled(arch, params, tokens, dict(c), precision)


def lr_at(step, o):
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine to 0."""
    s = float(step)
    if s < o["warmup"]:
        return o["lr"] * s / max(o["warmup"], 1)
    frac = min(max((s - o["warmup"]) / max(o["total"] - o["warmup"], 1),
                   0.0), 1.0)
    return 0.5 * o["lr"] * (1 + math.cos(math.pi * frac))


@functools.partial(jax.jit,
                   static_argnames=("arch", "c", "precision", "dtypes"),
                   donate_argnums=(0, 1, 2))
def _adam_step(params, m, v, batch, lr, t, clip, arch, c, precision, dtypes):
    c = dict(c)
    val, g = jax.value_and_grad(loss, argnums=1)(arch, params, batch, c,
                                                 precision)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)), g)
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    mh, vh = 1 / (1 - b1 ** t), 1 / (1 - b2 ** t)
    # each parameter is stored in its own dtype after each update
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        (p - lr * a * mh / (jnp.sqrt(b * vh) + eps)).astype(dt).astype(F32)
        for p, a, b, dt in zip(leaves, jax.tree.leaves(m),
                               jax.tree.leaves(v), dtypes)])
    norms = [jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(g)]
    return params, m, v, val, norms


def train_steps(arch, params0, batches, cfg, opt, precision="float32"):
    """Follow the program's first steps from the same weights and batches,
    through ``arch``'s forward.

    Returns the loss of each step, the clipped gradient's per-leaf norms at
    step 1 and the per-leaf norms of the change of the parameters after the
    last step, all as numpy.
    """
    c = arch.constants(cfg)
    p = jax.tree.map(lambda x: jnp.array(x, F32, copy=True), params0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        b = {k: jnp.asarray(x) for k, x in b.items()}
        p, m, v, val, norms = _adam_step(
            p, m, v, b, lr_at(i, opt), float(i + 1), float(opt["clip"]),
            arch=arch, c=c, precision=precision,
            dtypes=tuple(str(x.dtype) for x in jax.tree.leaves(params0)))
        losses.append(float(val))
        if grad_norms is None:
            grad_norms = np.asarray([float(n) for n in norms])
    del m, v
    start = jax.tree.leaves(params0)
    change = np.asarray([float(jnp.sqrt(jnp.sum(
        (a - b.astype(F32)) ** 2))) for a, b in zip(jax.tree.leaves(p), start)])
    return np.asarray(losses), grad_norms, change

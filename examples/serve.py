"""Batched serving example: prefill a batch of prompts, decode new tokens.

Exercises the same prefill/decode_step paths the dry-run lowers for the
decode_32k / long_500k cells (KV cache for attention archs, O(1) state
for SSM archs).

Head (``--head {full,lsh}``):
  full   the baseline O(V·d)-per-token head: full logits matmul + argmax.
  lsh    the LSH-shortlisted head (``repro/models/sampled_softmax.py``):
         a MIPS index over the lm_head rows is probed with the decode
         hidden state, up to ``shortlist_per_table`` candidates are
         gathered per (probe, table) pair and the argmax runs over that
         static shortlist only — O(J·L·c·d) per token.  Approximate:
         when no probed bucket holds the true argmax the emitted token
         differs from ``--head full`` (the bias boundary documented in
         docs/ARCHITECTURE.md; recall@k is pinned in tests and gated by
         ``benchmarks/run.py tab_softmax``).

Timing is reported PER PHASE — prefill seconds and the decode p10/p50
ms/token over the per-step latencies (p10 ≈ the steady-state floor once
compilation and cache effects settle; the first, compile-carrying step
is timed separately) — so the shortlist head has a comparable baseline.

Run:  PYTHONPATH=src python examples/serve.py [--arch zamba2_1_2b]
          [--new-tokens 32] [--head lsh]
(uses the arch's SMOKE config so it runs on CPU).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import (
    LMHeadIndex, SampledSoftmaxConfig, decode_step, init_cache, init_params,
    lsh_decode_step, prefill,
)
from repro.launch.cache import enable_compile_cache


def _percentiles(ms):
    """(p10, p50) of per-step latencies, excluding the compile step."""
    steady = ms[1:] if len(ms) > 1 else ms
    return (float(np.percentile(steady, 10)),
            float(np.percentile(steady, 50)))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2_1_2b",
                    choices=configs.all_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--head", default="full", choices=["full", "lsh"],
                    help="full: O(V) logits matmul per token; lsh: "
                         "LSH-shortlisted argmax over probed candidates")
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    b, s = args.batch, args.prompt_len
    max_len = s + args.new_tokens

    batch = {}
    if cfg.frontend == "embed_stub":
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab)
    if "cross_attn" in cfg.block_pattern:
        batch["image_embeds"] = jax.random.normal(
            key, (b, cfg.n_patches, cfg.d_model))

    head = None
    if args.head == "lsh":
        # Decode wants RECALL, not exact probabilities, so the shortlist
        # runs on the norm-ranged (banded) MIPS index: one global
        # Simple-LSH scale caps an exact-match query's per-table
        # collision at cos ~ ||x||/M (measured recall ~0.5 on an init
        # head); per-band scales restore it (~0.98 — see
        # benchmarks/run.py tab_softmax).  k sized so each band's mean
        # bucket occupancy stays within shortlist_per_table.
        from repro.core.families import get_family
        fam = get_family("mips_banded")
        band_rows = max(1, cfg.vocab // fam.num_bands())
        scfg = SampledSoftmaxConfig(
            family="mips_banded",
            k=max(3, band_rows.bit_length() - 3),
            l=8, multiprobe=2, shortlist_per_table=8)
        head = LMHeadIndex(params, cfg, scfg)
        n_cand = (fam.num_bands() * (1 + scfg.multiprobe) * scfg.l
                  * scfg.shortlist_per_table)
        print(f"[{cfg.name}] head=lsh: {head.index.n_points} rows x "
              f"{head.index.n_tables} tables, "
              f"shortlist {n_cand}/{cfg.vocab} candidates/token")

    cache = init_cache(cfg, b, max_len)
    t0 = time.perf_counter()
    h, cache = prefill(params, cfg, batch, cache)
    jax.block_until_ready(h)
    prefill_s = time.perf_counter() - t0
    print(f"[{cfg.name}] prefill {b}x{s}: {prefill_s:.2f}s")

    if args.head == "lsh":
        scfg_ = head.scfg
        step_fn = jax.jit(
            lambda prm, st, c, idx: lsh_decode_step(prm, cfg, scfg_, st, c,
                                                    idx))
    else:
        def _full_step(prm, st, c):
            logits, c2 = decode_step(prm, cfg, st, c)
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), c2
        step_fn = jax.jit(_full_step)

    tok = jnp.zeros((b, 1), jnp.int32)
    emb = jnp.zeros((b, 1, cfg.d_model))
    generated = []
    step_ms = []
    t_loop = time.perf_counter()
    for t in range(args.new_tokens):
        step = {"positions": jnp.full((b, 1), s + t, jnp.int32)}
        if cfg.frontend == "embed_stub":
            step["embeds"] = emb
        else:
            step["tokens"] = tok
        if "cross_attn" in cfg.block_pattern:
            step["image_embeds"] = batch["image_embeds"]
        t0 = time.perf_counter()
        if args.head == "lsh":
            tok, cache = step_fn(params, step, cache, head.index)
        else:
            tok, cache = step_fn(params, step, cache)
        jax.block_until_ready(tok)
        step_ms.append((time.perf_counter() - t0) * 1e3)
        if cfg.frontend == "embed_stub":
            emb = jax.random.normal(jax.random.fold_in(key, t),
                                    (b, 1, cfg.d_model))
        generated.append(tok[:, 0])
    dt = time.perf_counter() - t_loop
    toks = jnp.stack(generated, axis=1)
    p10, p50 = _percentiles(step_ms)
    print(f"[{cfg.name}] decode head={args.head}: p10 {p10:.2f} ms/token  "
          f"p50 {p50:.2f} ms/token  (compile step {step_ms[0]:.1f} ms)")
    print(f"decoded {args.new_tokens} tokens/seq in {dt:.2f}s "
          f"({b*args.new_tokens/dt:.1f} tok/s); sample row: "
          f"{toks[0][:12].tolist()}")


if __name__ == "__main__":
    main()

"""Benchmark harness — one function per paper table/figure.

  fig9_sample_quality      gradient-norm + cosine similarity of LGD vs SGD
                           samples (paper Fig. 9 a-f), 3 datasets
  fig10_convergence        LGD vs SGD convergence, epoch-wise AND
                           wall-clock (paper Fig. 10/11)
  fig12_adagrad            LGD+AdaGrad vs SGD+AdaGrad (paper Fig. 12/13)
  tab_sampling_cost        per-iteration sampling cost: uniform vs LSH
                           lookup vs full near-neighbour scan (Sec. 2.2.1)
  tab_refresh_cost         index refresh wall time: full re-embed/re-hash
                           vs dirty-fraction delta refresh
  fig5_lm_epochwise        deep-model LGD (BERT-analogue): LSH-sampled LM
                           fine-tuning vs uniform, epoch-wise loss
  tab_train_step           end-to-end Trainer step: uniform vs sharded-LGD
                           (device-resident batches) step wall time,
                           sampler-overhead fraction, estimator variance
  tab_robustness           degradation-ladder step cost: healthy vs
                           stale-index vs uniform-fallback Trainer step
                           time, plus recovery latency after an injected
                           refresh-failure burst
  tab_multihost            multi-host deployment: real 2-process
                           jax.distributed step time vs a one-process
                           2-shard baseline, plus reform-time-to-
                           first-step after a host kill
  tab_optimizers           adaptive optimisers (momentum/AdaGrad/Adam)
                           under LGD: per-optimizer step time + estimator
                           variance, and multi-probe vs single-probe
                           fallback rate on a skewed corpus
  tab_families             SRP vs asymmetric-MIPS hash families on an
                           un-normalised corpus: per-draw sampling cost
                           + estimator variance vs uniform
  tab_softmax              LSH-sampled softmax head vs the full-vocab
                           O(V) head: train step time ratio, decode
                           shortlist vs full matmul (measured + roofline
                           projection at V=131k), normaliser-estimate
                           bias, shortlist recall
  thm2_variance            empirical Tr(Cov) of LGD vs SGD estimators

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's
headline quantity).  Full curves land in benchmarks/results/*.json.

CLI: ``python benchmarks/run.py [table ...] [--quick]`` — no tables =
run everything.  ``--quick`` shrinks problem sizes/iterations to a CI
CPU budget (used by the bench-regression gate together with
``benchmarks/check_regression.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    LGDProblem,
    LSHParams,
    IndexMutation,
    mutate_index,
    init as lgd_init,
    lgd_step,
    full_loss,
    regression_query,
    sgd_step,
)
import repro.core.estimator as E
import repro.core.sampler as S
from repro.core.lgd import preprocess_regression, squared_loss_grad
from repro.data import make_regression, make_token_corpus, uniform_batches
from repro.data.lsh_pipeline import (
    LSHPipelineConfig,
    LSHSampledPipeline,
    ShardedLSHPipeline,
    lm_head_query_fn,
    mean_pool_feature_fn,
)
from repro.models import ModelConfig, forward, init_params, loss as lm_loss
from repro.optim import SGD, AdaGrad, Adam, apply_updates
from repro.train import Trainer, TrainerConfig
from repro.launch.cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "results")
KEY = jax.random.PRNGKey(0)


def _build_index(key, x_aug, p, **kw):
    return mutate_index(
        None, IndexMutation("build", key=key, x_aug=x_aug), p, **kw)

DATASETS = {
    "yearmsd-like": dict(d=90, noise="pareto"),
    "slice-like": dict(d=74, noise="clustered"),
    "ujiindoor-like": dict(d=64, noise="pareto"),
}


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


def _dataset(name, n=8000, seed=42):
    # seeds pinned: the LGD-vs-SGD gaps are real but modest, so the
    # calibrated dataset draws are part of the experiment definition
    # (see EXPERIMENTS.md §Repro).
    spec = DATASETS[name]
    ds = make_regression(jax.random.PRNGKey(seed), name, n_train=n,
                         n_test=n // 8, **spec)
    return ds


def fig9_sample_quality():
    out = {}
    for name in DATASETS:
        ds = _dataset(name)
        xt, yt, x_aug = preprocess_regression(ds.x_train, ds.y_train)
        theta, *_ = jnp.linalg.lstsq(xt, yt)   # 'freeze after 1/4 epoch'
        p = LSHParams(k=5, l=100, dim=xt.shape[1] + 1, family="quadratic")
        index = _build_index(jax.random.PRNGKey(1), x_aug, p)
        q = regression_query(theta)
        t0 = time.perf_counter()
        res = S.sample(jax.random.PRNGKey(2), index, x_aug, q, p, m=1024)
        us = (time.perf_counter() - t0) / 1024 * 1e6
        gn = jax.vmap(lambda i: jnp.linalg.norm(
            squared_loss_grad(theta, xt[i], yt[i])))
        lgd_n = float(jnp.mean(gn(res.indices)))
        unif = jax.random.randint(jax.random.PRNGKey(3), (1024,), 0,
                                  xt.shape[0])
        sgd_n = float(jnp.mean(gn(unif)))
        full_grad = jnp.mean(jax.vmap(
            lambda a, b: squared_loss_grad(theta, a, b))(xt, yt), 0)

        def mean_cos(idx, probs=None):
            g = jax.vmap(lambda i: squared_loss_grad(theta, xt[i], yt[i])
                         )(idx)
            if probs is not None:
                g = g / (probs[:, None] * xt.shape[0])
            g16 = g[: (len(idx) // 16) * 16].reshape(-1, 16, g.shape[-1]
                                                     ).mean(1)
            return float(jnp.mean(
                jnp.sum(g16 * full_grad, -1) /
                (jnp.linalg.norm(g16, axis=-1)
                 * jnp.linalg.norm(full_grad) + 1e-30)))

        cos_lgd = mean_cos(res.indices, res.probs)
        cos_sgd = mean_cos(unif)
        out[name] = dict(lgd_norm=lgd_n, sgd_norm=sgd_n,
                         cos_lgd=cos_lgd, cos_sgd=cos_sgd)
        _row(f"fig9_norm_ratio[{name}]", us, f"{lgd_n / sgd_n:.3f}")
        _row(f"fig9_cos_gain[{name}]", us, f"{cos_lgd - cos_sgd:+.4f}")
    return out


def _convergence(optimizer, tag, steps=600):
    out = {}
    for name in DATASETS:
        ds = _dataset(name)
        prob = LGDProblem(
            kind="regression",
            lsh=LSHParams(k=5, l=100, dim=ds.x_train.shape[1] + 1,
                          family="quadratic"),
            minibatch=16)
        state, xt, yt, xa = lgd_init(
            jax.random.PRNGKey(4), prob, ds.x_train, ds.y_train, optimizer)
        sL = sU = state
        tL = tU = 0.0
        curveL, curveU = [], []
        # warm up jits out of the timed region
        lgd_step(KEY, sL, xt, yt, xa, prob, optimizer)
        sgd_step(KEY, sU, xt, yt, prob, optimizer)
        for i in range(steps):
            kk = jax.random.fold_in(KEY, i)
            t0 = time.perf_counter()
            sL, _ = lgd_step(kk, sL, xt, yt, xa, prob, optimizer)
            jax.block_until_ready(sL.theta)
            tL += time.perf_counter() - t0
            t0 = time.perf_counter()
            sU, _ = sgd_step(kk, sU, xt, yt, prob, optimizer)
            jax.block_until_ready(sU.theta)
            tU += time.perf_counter() - t0
            if i % 50 == 49:
                curveL.append(float(full_loss(sL.theta, xt, yt, prob)))
                curveU.append(float(full_loss(sU.theta, xt, yt, prob)))
        out[name] = dict(lgd=curveL, sgd=curveU, t_lgd=tL, t_sgd=tU)
        _row(f"{tag}_final_loss_ratio[{name}]", tL / steps * 1e6,
             f"{curveL[-1] / max(curveU[-1], 1e-12):.3f}")
        _row(f"{tag}_time_overhead[{name}]", tU / steps * 1e6,
             f"{tL / max(tU, 1e-9):.2f}x")
    return out


def fig10_convergence():
    return _convergence(SGD(lr=5e-2), "fig10")


def fig12_adagrad():
    return _convergence(AdaGrad(lr=5e-2), "fig12")


def _timed(fn, iters, *, key_arg=True):
    """us/call of a jitted thunk (optionally re-keyed per call)."""
    jax.block_until_ready(fn(KEY) if key_arg else fn())   # warm up jit
    t0 = time.perf_counter()
    for i in range(iters):
        out = fn(jax.random.fold_in(KEY, i)) if key_arg else fn()
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def tab_sampling_cost(quick: bool = False):
    """Sec 2.2/2.2.1: LSH sampling must be O(1)-ish; near-neighbour is not.

    Also the BENCH trajectory for the fused fast path: hashing stage
    (XLA reference vs fused simhash kernel), probe stage (per-table
    binary-search reference vs fused bucket-probe), and the per-query
    amortisation of ``sample_batched``.  On CPU hosts the "fused" path
    auto-falls back to XLA (``default_use_pallas()``), so ref-vs-fused
    there measures dispatch parity, not kernel speedup — the JSON
    records the backend so the trajectory is comparable across hosts.
    """
    from repro.core import bucket_bounds, bucket_bounds_batched, query_codes
    from repro.kernels import default_use_pallas
    from repro.kernels.simhash import simhash_codes

    n_pts = 8192 if quick else 32768
    iters = 150 if quick else 200
    probe_iters = 30 if quick else 50
    hash_iters = 8 if quick else 20
    ds = _dataset("yearmsd-like", n=n_pts)
    xt, yt, x_aug = preprocess_regression(ds.x_train, ds.y_train)
    d = xt.shape[1]
    n = x_aug.shape[0]
    p = LSHParams(k=5, l=100, dim=d + 1, family="sparse")
    index = _build_index(jax.random.PRNGKey(5), x_aug, p)
    theta = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (d,))
    q = regression_query(theta)
    B = 64
    queries = q[None] + 0.01 * jax.random.normal(
        jax.random.PRNGKey(7), (B, d + 1))

    # --- per-step sampling cost -------------------------------------------
    us_uniform = _timed(
        jax.jit(lambda k: jax.random.randint(k, (1,), 0, n)), iters)

    # ref and fused interleaved in one loop so machine-load drift hits
    # both equally; the 10th-percentile per-call time (robust min, not
    # mean) so GC pauses and CI noisy-neighbour spikes cannot flip the
    # regression gate's ratios.
    ref_fn = lambda k: S.sample(k, index, x_aug, q, p, m=1,        # noqa: E731
                                use_pallas=False).indices
    fused_fn = lambda k: S.sample(k, index, x_aug, q, p,           # noqa: E731
                                  m=1).indices
    jax.block_until_ready(ref_fn(KEY))
    jax.block_until_ready(fused_fn(KEY))
    dt_ref, dt_fused = [], []
    for i in range(iters):
        kk = jax.random.fold_in(KEY, i)
        t0 = time.perf_counter()
        jax.block_until_ready(ref_fn(kk))
        dt_ref.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fused_fn(kk))
        dt_fused.append(time.perf_counter() - t0)
    us_lgd_ref = float(np.percentile(dt_ref, 10)) * 1e6
    us_lgd_fused = float(np.percentile(dt_fused, 10)) * 1e6

    batched_fn = jax.jit(
        lambda k: S.sample_batched(k, index, x_aug, queries, p, m=1).indices)
    jax.block_until_ready(batched_fn(KEY))
    dt_b = []
    for i in range(probe_iters):
        kk = jax.random.fold_in(KEY, i)
        t0 = time.perf_counter()
        jax.block_until_ready(batched_fn(kk))
        dt_b.append(time.perf_counter() - t0)
    us_batched = float(np.percentile(dt_b, 10)) * 1e6 / B

    # --- stage timings: hashing (index build/refresh hot op) ---------------
    us_hash_ref = _timed(
        lambda: query_codes(index, x_aug, p), hash_iters, key_arg=False)
    us_hash_fused = _timed(
        lambda: simhash_codes(x_aug, index.projections, k=p.k, l=p.l,
                              use_pallas=default_use_pallas()),
        hash_iters, key_arg=False)

    # --- stage timings: probing (hash + bucket search, B queries) ----------
    # queries passed as a real argument so XLA cannot constant-fold the
    # closed-over batch into the compiled program.  Ref and dispatched
    # paths are INTERLEAVED in one loop with 10th-percentile stats —
    # sequential loops let machine-load drift masquerade as a dispatch
    # regression (the pre-PR3 baseline recorded exactly that artifact),
    # and the regression gate asserts the dispatched path never loses.
    probe_ref_j = jax.jit(lambda qs: jax.vmap(
        lambda c: bucket_bounds(index, c))(query_codes(index, qs, p)))
    probe_fused_j = jax.jit(
        lambda qs: bucket_bounds_batched(index, qs, p))
    jax.block_until_ready(probe_ref_j(queries))
    jax.block_until_ready(probe_fused_j(queries))
    dt_pr, dt_pf = [], []
    for _ in range(probe_iters):
        t0 = time.perf_counter()
        jax.block_until_ready(probe_ref_j(queries))
        dt_pr.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(probe_fused_j(queries))
        dt_pf.append(time.perf_counter() - t0)
    us_probe_ref = float(np.percentile(dt_pr, 10)) * 1e6 / B
    us_probe_fused = float(np.percentile(dt_pf, 10)) * 1e6 / B

    # near-neighbour baseline: full O(N d) scan for the max inner product
    us_nn = _timed(jax.jit(lambda: jnp.argmax(x_aug @ q)), probe_iters,
                   key_arg=False)

    _row("sampling_cost_uniform", us_uniform, "baseline")
    _row("sampling_cost_lgd_ref", us_lgd_ref,
         f"{us_lgd_ref / us_uniform:.1f}x uniform")
    _row("sampling_cost_lgd_fused", us_lgd_fused,
         f"{us_lgd_ref / max(us_lgd_fused, 1e-9):.2f}x ref")
    _row("sampling_cost_lgd_batched", us_batched,
         f"{us_lgd_fused / max(us_batched, 1e-9):.1f}x scalar")
    _row("sampling_cost_hash_fused", us_hash_fused,
         f"{us_hash_ref / max(us_hash_fused, 1e-9):.2f}x ref")
    _row("sampling_cost_probe_fused", us_probe_fused,
         f"{us_probe_ref / max(us_probe_fused, 1e-9):.2f}x ref")
    _row("sampling_cost_full_scan", us_nn,
         f"{us_nn / max(us_lgd_fused, 1e-9):.1f}x lgd")

    out = {
        "backend": jax.default_backend(),
        "fused_is_pallas": default_use_pallas(),
        "quick": quick,
        "n_points": n, "n_tables": p.l, "k": p.k, "query_batch": B,
        "us_per_call": {
            "uniform": us_uniform,
            "lsh_reference": us_lgd_ref,
            "lsh_fused": us_lgd_fused,
            "lsh_fused_batched_per_query": us_batched,
            "full_scan": us_nn,
        },
        "hash_stage_us": {"reference": us_hash_ref, "fused": us_hash_fused,
                          "speedup": us_hash_ref / max(us_hash_fused, 1e-9)},
        "probe_stage_us_per_query": {
            "reference": us_probe_ref, "fused": us_probe_fused,
            "speedup": us_probe_ref / max(us_probe_fused, 1e-9)},
    }
    os.makedirs(RESULTS, exist_ok=True)
    # sampling_cost.json is EXCLUSIVELY the CI regression-gate baseline
    # (quick mode, so CI compares like-for-like problem sizes);
    # BENCH_sampling.json keeps the full-mode trajectory record.  Never
    # cross-write: a full-mode run overwriting the gate baseline would
    # arbitrarily retune the 25% band.
    fname = "sampling_cost.json" if quick else "BENCH_sampling.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_refresh_cost(quick: bool = False):
    """Index-refresh wall time: full re-embed/re-hash vs delta refresh.

    The paper amortises preprocessing because "the representations do
    not change rapidly" — the delta path takes that literally: only the
    rows visited since the last refresh (a dirty fraction of the shard)
    are re-embedded and re-hashed, then merged into the sorted index
    through the previous order.  This table pins the claim that delta
    cost scales with the dirty fraction, not with N: the regression
    gate requires >= 2x over full refresh at 10% dirty.

    Measured on the LM feature path (pooled last-layer reps — the
    re-embed IS the dominant term, exactly the deep-model regime the
    delta path exists for); timings are medians over repeated refreshes
    at fixed params so full and delta see identical work per call.
    """
    cfg = ModelConfig(
        name="lm-refresh", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, chunk=16, loss_chunk=64,
        dtype="float32", rope_theta=10000.0)
    n_corpus = 1024 if quick else 4096
    iters = 4 if quick else 8
    fracs = (0.01, 0.10, 0.50)
    corpus = make_token_corpus(29, n_corpus, 24, cfg.vocab, hard_frac=0.12)
    params = init_params(KEY, cfg)
    pipe = LSHSampledPipeline(
        jax.random.PRNGKey(31), corpus.tokens, mean_pool_feature_fn(cfg),
        lm_head_query_fn(),
        LSHPipelineConfig(k=5, l=10, minibatch=16, refresh_every=0,
                          refresh_mode="delta", drift_frac=0.0),
        params=params)
    n = pipe.n

    def timed_refresh(full, frac=None):
        def arm():
            if frac is not None:
                # exact dirty fraction, deterministic: first frac*n rows
                mask = jnp.arange(n) < max(int(frac * n), 1)
                pipe._dirty = mask
        arm()
        pipe.refresh(full=full)                     # warm up jit caches
        dts = []
        for _ in range(iters):
            arm()
            t0 = time.perf_counter()
            pipe.refresh(full=full)
            jax.block_until_ready((pipe.index.sorted_codes, pipe.features))
            dts.append(time.perf_counter() - t0)
        return float(np.median(dts)) * 1e6

    us_full = timed_refresh(full=True)
    delta_us = {f"{f:.2f}": timed_refresh(full=False, frac=f)
                for f in fracs}
    speedup_01 = us_full / max(delta_us["0.10"], 1e-9)

    _row("tab_refresh_full", us_full, "baseline")
    for f in fracs:
        k = f"{f:.2f}"
        _row(f"tab_refresh_delta[{k}]", delta_us[k],
             f"{us_full / max(delta_us[k], 1e-9):.2f}x full")
    out = {
        "backend": jax.default_backend(),
        "quick": quick, "n_points": n, "k": 5, "l": 10,
        "refresh_us": {"full": us_full, "delta": delta_us},
        "delta_speedup_at_0.10": speedup_01,
    }
    os.makedirs(RESULTS, exist_ok=True)
    # refresh_cost.json is the CI regression-gate baseline (quick mode);
    # BENCH_refresh.json keeps the full-mode trajectory record.
    fname = "refresh_cost.json" if quick else "BENCH_refresh.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_streaming(quick: bool = False):
    """Streaming append under live traffic vs a full index rebuild.

    The index-mutation API promises that growing the corpus does NOT
    cost a rebuild: appending a chunk embeds/hashes only the new rows
    and tie-stably merges them through the previous sort order, while
    draws keep flowing between chunks.  This table appends 10% of the
    corpus in chunks with a batch drawn after every chunk (the "live
    traffic"), timing only the appends, and compares the TOTAL against
    one full refresh of the final corpus (re-embed + re-hash + re-sort
    of every live row).  The regression gate caps the ratio at 0.5x:
    streaming in a tenth of the corpus must cost at most half a
    rebuild, or the amortisation story is broken.

    Measured on the LM feature path (pooled last-layer reps, the
    deep-model regime where re-embedding dominates), same geometry as
    tab_refresh_cost so the two tables read together.
    """
    cfg = ModelConfig(
        name="lm-streaming", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, chunk=16, loss_chunk=64,
        dtype="float32", rope_theta=10000.0)
    n0 = 1536 if quick else 3584           # capacity 2048 / 4096: the
    chunk_rows = 32                        # 10% append fits with no
    n_app = (n0 // 10) // chunk_rows * chunk_rows   # growth recompile
    iters = 4 if quick else 8
    corpus = make_token_corpus(41, n0 + n_app + chunk_rows, 24,
                               cfg.vocab, hard_frac=0.12)
    params = init_params(KEY, cfg)
    pipe = LSHSampledPipeline(
        jax.random.PRNGKey(43), corpus.tokens[:n0],
        mean_pool_feature_fn(cfg), lm_head_query_fn(),
        LSHPipelineConfig(k=5, l=10, minibatch=16, refresh_every=0,
                          streaming=True),
        params=params)

    # warm up the append/evict/draw programs off the clock, then return
    # the window to its starting membership.
    warm = corpus.tokens[n0 + n_app:n0 + n_app + chunk_rows]
    gids = pipe.append_rows(warm)
    pipe.next_batch()
    pipe.evict_rows(gids)
    jax.block_until_ready(pipe.index.sorted_codes)

    t_app = 0.0
    for s in range(0, n_app, chunk_rows):
        chunk = corpus.tokens[n0 + s:n0 + s + chunk_rows]
        t0 = time.perf_counter()
        pipe.append_rows(chunk)
        jax.block_until_ready(pipe.index.sorted_codes)
        t_app += time.perf_counter() - t0
        pipe.next_batch()                  # live traffic, untimed
    us_append = t_app * 1e6
    assert pipe.n_live == n0 + n_app

    pipe.refresh(full=True)                # warm up jit caches
    dts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        pipe.refresh(full=True)
        jax.block_until_ready((pipe.index.sorted_codes, pipe.features))
        dts.append(time.perf_counter() - t0)
    us_rebuild = float(np.median(dts)) * 1e6
    ratio = us_append / max(us_rebuild, 1e-9)

    # eviction is a device-side sentinel merge — reported for the
    # record, ungated (no rebuild-equivalent to normalise against).
    # Chunked like the appends so the warmed merge shape is reused.
    evict_ids = np.arange(n_app, dtype=np.int64) + pipe.example_offset \
        + n0
    t_ev = 0.0
    for s in range(0, n_app, chunk_rows):
        t0 = time.perf_counter()
        pipe.evict_rows(evict_ids[s:s + chunk_rows])
        jax.block_until_ready(pipe.index.sorted_codes)
        t_ev += time.perf_counter() - t0
    us_evict = t_ev * 1e6

    _row("tab_streaming_rebuild", us_rebuild, "baseline")
    _row("tab_streaming_append[0.10]", us_append,
         f"{ratio:.2f}x of full rebuild")
    _row("tab_streaming_evict[0.10]", us_evict, "sentinel merge")
    out = {
        "backend": jax.default_backend(),
        "quick": quick, "n0": n0, "n_appended": n_app, "k": 5, "l": 10,
        "append_us_total": us_append,
        "rebuild_us": us_rebuild,
        "evict_us": us_evict,
        "append_vs_rebuild": ratio,
    }
    os.makedirs(RESULTS, exist_ok=True)
    # streaming.json is the CI regression-gate baseline (quick mode);
    # BENCH_streaming.json keeps the full-mode trajectory record.
    fname = "streaming.json" if quick else "BENCH_streaming.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


def fig5_lm_epochwise(steps=240):
    """Deep-model LGD: LSH-sampled LM training vs uniform sampling."""
    cfg = ModelConfig(
        name="lm-bench", n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
        d_ff=256, vocab=512, chunk=32, loss_chunk=64, dtype="float32",
        rope_theta=10000.0)
    corpus = make_token_corpus(7, 2048, 32, cfg.vocab, hard_frac=0.12)
    eval_batch = {
        "tokens": jnp.asarray(corpus.tokens[:256, :-1]),
        "targets": jnp.asarray(corpus.tokens[:256, 1:]),
    }

    def run(use_lgd):
        params = init_params(KEY, cfg)
        if use_lgd:
            def feature_fn(p, tokens):
                h = forward(p, cfg, {"tokens": tokens})
                return jnp.mean(h.astype(jnp.float32), axis=1)

            def query_fn(p):
                w = p["embed_group"]["lm_head"].astype(jnp.float32)
                return jnp.mean(w, axis=1)

            pipe = LSHSampledPipeline(
                jax.random.PRNGKey(8), corpus.tokens, jax.jit(feature_fn),
                query_fn, LSHPipelineConfig(k=7, l=10, minibatch=16,
                                            refresh_every=100),
                params=params)
            batches = iter(pipe.next_batch, None)
        else:
            batches = uniform_batches(corpus, 16, seed=9)
        tr = Trainer(cfg, params, Adam(lr=3e-3), batches,
                     TrainerConfig(log_every=1000, donate=False))
        eval_fn = jax.jit(lambda p: lm_loss(p, cfg, eval_batch))
        curve = []
        t0 = time.perf_counter()
        for _ in range(steps // 40):
            tr.run(40)
            curve.append(float(eval_fn(tr.params)))
        return curve, time.perf_counter() - t0

    curve_lgd, t_lgd = run(True)
    curve_uni, t_uni = run(False)
    _row("fig5_lm_final_loss_lgd", t_lgd / steps * 1e6,
         f"{curve_lgd[-1]:.4f}")
    _row("fig5_lm_final_loss_uniform", t_uni / steps * 1e6,
         f"{curve_uni[-1]:.4f}")
    return dict(lgd=curve_lgd, uniform=curve_uni, t_lgd=t_lgd, t_uni=t_uni)


def tab_train_step(quick: bool = False):
    """End-to-end Trainer step: uniform vs sharded LGD (2 shards).

    Two headline quantities for the paper's wall-clock claim at the
    TRAINING level (not just the sampling microbenchmark):
      * mean step wall time after warmup — LGD's per-step overhead is
        the O(1) hash lookup + host-side batch assembly, with the
        periodic index refresh double-buffered onto a host thread;
      * minibatch estimator variance — Var of the importance-weighted
        batch loss across repeated draws at FIXED params, vs Var of the
        uniform batch loss (the paper's adaptive-sampling variance win,
        Thm 2, measured end-to-end through the LM loss).
    """
    cfg = ModelConfig(
        name="lm-train-step", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, chunk=16, loss_chunk=64,
        dtype="float32", rope_theta=10000.0)
    n_corpus, batch = (512, 16) if quick else (2048, 32)
    steps = 16 if quick else 48
    var_draws = 24 if quick else 96
    corpus = make_token_corpus(17, n_corpus, 24, cfg.vocab, hard_frac=0.12)

    def make_trainer(use_lgd, params):
        if use_lgd:
            sampler = ShardedLSHPipeline(
                jax.random.PRNGKey(21), corpus.tokens,
                mean_pool_feature_fn(cfg), lm_head_query_fn(),
                LSHPipelineConfig(k=5, l=10, minibatch=batch,
                                  refresh_every=max(steps // 2, 8),
                                  refresh_async=True),
                n_shards=2, params=params)
            return Trainer(cfg, params, Adam(lr=3e-3),
                           tcfg=TrainerConfig(log_every=10_000),
                           sampler=sampler), sampler
        return Trainer(cfg, params, Adam(lr=3e-3),
                       batches=uniform_batches(corpus, batch, seed=22),
                       tcfg=TrainerConfig(log_every=10_000,
                                          donate=False)), None

    # uniform and LGD trainers step ALTERNATELY in one loop with
    # 10th-percentile per-step stats, so machine-load drift hits both
    # equally and the gated overhead ratio stays stable (sequential
    # whole-run timing put ~30% run-to-run swings on the ratio).
    tr_uni, _ = make_trainer(False, init_params(KEY, cfg))
    tr_lgd, sampler = make_trainer(True, init_params(KEY, cfg))
    tr_uni.run(4)                                   # warm up jit + caches
    tr_lgd.run(4)
    d0_uni, d0_lgd = tr_uni.data_seconds, tr_lgd.data_seconds
    dts_uni, dts_lgd = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        tr_uni.run(1)
        dts_uni.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        tr_lgd.run(1)
        dts_lgd.append(time.perf_counter() - t0)
    us_uni = float(np.percentile(dts_uni, 10)) * 1e6
    us_lgd = float(np.percentile(dts_lgd, 10)) * 1e6
    # host-blocking batch-draw fraction: the device-resident data path's
    # headline — drawing a batch is one compiled-call dispatch, not
    # host-side assembly.
    frac_uni = (tr_uni.data_seconds - d0_uni) / max(sum(dts_uni), 1e-12)
    frac_lgd = (tr_lgd.data_seconds - d0_lgd) / max(sum(dts_lgd), 1e-12)
    tr_uni.finalize()

    # estimator variance at the FINAL LGD params, same params both ways
    params = tr_lgd.params
    loss_j = jax.jit(lambda b: lm_loss(params, cfg, b))
    sampler.set_params(params)
    draws_lgd = [float(loss_j(sampler.next_batch()))
                 for _ in range(var_draws)]
    uni = uniform_batches(corpus, batch, seed=23)
    draws_uni = [float(loss_j(next(uni))) for _ in range(var_draws)]
    var_lgd = float(np.var(draws_lgd))
    var_uni = float(np.var(draws_uni))
    sampler.finalize()

    _row("tab_train_step_uniform", us_uni, "baseline")
    _row("tab_train_step_lgd", us_lgd,
         f"{us_lgd / max(us_uni, 1e-9):.2f}x uniform")
    _row("tab_train_step_sampler_frac", us_lgd * frac_lgd,
         f"{frac_lgd:.3f} of step")
    _row("tab_train_step_var_ratio", 0.0,
         f"{var_lgd / max(var_uni, 1e-30):.3f}")
    out = {
        "backend": jax.default_backend(),
        "quick": quick, "batch": batch, "n_corpus": n_corpus,
        "steps_timed": steps, "n_shards": 2,
        "step_us": {"uniform": us_uni, "lgd": us_lgd,
                    "overhead": us_lgd / max(us_uni, 1e-9)},
        # device-resident step path: batches are drawn/gathered/weighted
        # on device; this column is the host-blocking draw fraction.
        "sampler_overhead_frac": {"uniform": frac_uni, "lgd": frac_lgd},
        "device_resident": True,
        "estimator_variance": {"lgd_weighted_loss": var_lgd,
                               "uniform_loss": var_uni,
                               "ratio": var_lgd / max(var_uni, 1e-30)},
        "mean_loss": {"lgd": float(np.mean(draws_lgd)),
                      "uniform": float(np.mean(draws_uni))},
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "train_step.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_robustness(quick: bool = False):
    """Degradation-ladder step cost + recovery latency (one table).

    Two gated quantities for the self-healing LGD story:
      * degraded-mode step time — Trainer step wall time with the
        sampler held in ``stale-index`` and ``uniform-fallback`` health
        states vs a healthy run, all three stepped ALTERNATELY in one
        loop with 10th-percentile stats (same discipline as
        ``tab_train_step``) — degraded modes are fallbacks, not slow
        paths, so each must stay within 1.1x of healthy;
      * recovery latency — steps from the first health transition away
        from ``healthy`` to the ``recovered`` transition after an
        injected bounded refresh-failure burst (the ladder must come
        back, and quickly, once the fault clears).
    """
    from repro.data import HealthConfig
    from repro.testing import RefreshRaise

    cfg = ModelConfig(
        name="lm-robustness", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, chunk=16, loss_chunk=64,
        dtype="float32", rope_theta=10000.0, lgd_enabled=True)
    n_corpus, batch = (512, 16) if quick else (2048, 32)
    steps = 16 if quick else 48
    refresh_every = 8
    corpus = make_token_corpus(17, n_corpus, 24, cfg.vocab, hard_frac=0.12)

    def make(health, injector=None, retries=1):
        params = init_params(KEY, cfg)
        sampler = ShardedLSHPipeline(
            jax.random.PRNGKey(21), corpus.tokens,
            mean_pool_feature_fn(cfg), lm_head_query_fn(),
            LSHPipelineConfig(k=5, l=10, minibatch=batch,
                              refresh_every=refresh_every,
                              refresh_async=True, refresh_backoff=0.0,
                              refresh_retries=retries, health=health),
            n_shards=2, params=params)
        if injector is not None:
            sampler.set_fault_injector(injector)
        tr = Trainer(cfg, params, Adam(lr=3e-3),
                     tcfg=TrainerConfig(log_every=10_000), sampler=sampler)
        return tr, sampler

    NEVER = 10 ** 9
    # healthy: faults off, ladder idle.
    tr_ok, _ = make(HealthConfig(fallback_spike=1.1))
    # stale-index: every refresh fails (injected), the ladder is pinned
    # below the fallback rung, so the run serves from the last good
    # index forever — the steady-state cost of a broken refresh worker.
    tr_stale, s_stale = make(
        HealthConfig(max_stale_refreshes=NEVER, fallback_spike=1.1),
        injector=RefreshRaise(cycles=NEVER), retries=0)
    # uniform-fallback: monitors forced onto the bottom rung (recovery
    # cadence pinned out of reach) — weight-1 uniform draws all the way.
    tr_uni, s_uni = make(
        HealthConfig(max_stale_refreshes=1, recover_after=NEVER,
                     fallback_spike=1.1))
    for shard in s_uni.shards:
        shard.health.note_refresh_failure(0, "benchmark: forced rung")
        shard.health.note_refresh_failure(0, "benchmark: forced rung")
    for shard in s_stale.shards:
        shard.health.note_refresh_failure(0, "benchmark: forced rung")

    trainers = {"healthy": tr_ok, "stale_index": tr_stale,
                "uniform_fallback": tr_uni}
    for tr in trainers.values():
        tr.run(4)                              # warm up jit + caches
    dts = {name: [] for name in trainers}
    for _ in range(steps):
        for name, tr in trainers.items():
            t0 = time.perf_counter()
            tr.run(1)
            dts[name].append(time.perf_counter() - t0)
    step_us = {name: float(np.percentile(v, 10)) * 1e6
               for name, v in dts.items()}
    for tr in trainers.values():
        tr.finalize()
    assert s_stale.health_state() == "stale-index", s_stale.health_state()
    assert s_uni.health_state() == "uniform-fallback", s_uni.health_state()

    # recovery latency: a BOUNDED failure burst (2 cycles per shard)
    # walks the ladder down to uniform-fallback, then the recovery
    # cadence rebuilds the index and the run returns to healthy.
    rec_steps = 60
    tr_rec, s_rec = make(
        HealthConfig(max_stale_refreshes=1, recover_after=8,
                     fallback_spike=1.1),
        injector=RefreshRaise(cycles=2), retries=0)
    tr_rec.run(rec_steps)
    tr_rec.finalize()
    trans = s_rec.health_summary()["transitions"]
    down = [t for t in trans if t[-2] != "healthy"]
    up = [t for t in trans if t[-2] == "healthy"]
    degraded_at = int(down[0][1]) if down else None
    recovered_at = int(up[0][1]) if up else None
    recovered = bool(up) and s_rec.health_state() == "healthy"
    latency = (recovered_at - degraded_at
               if recovered and degraded_at is not None else None)

    ok_us = max(step_us["healthy"], 1e-9)
    _row("tab_robustness_healthy", step_us["healthy"], "baseline")
    _row("tab_robustness_stale_index", step_us["stale_index"],
         f"{step_us['stale_index'] / ok_us:.2f}x healthy")
    _row("tab_robustness_uniform_fallback", step_us["uniform_fallback"],
         f"{step_us['uniform_fallback'] / ok_us:.2f}x healthy")
    _row("tab_robustness_recovery", 0.0,
         f"{latency} steps to recover" if recovered else "NOT RECOVERED")
    out = {
        "backend": jax.default_backend(),
        "quick": quick, "batch": batch, "n_corpus": n_corpus,
        "steps_timed": steps, "n_shards": 2,
        "refresh_every": refresh_every,
        "step_us": step_us,
        "degraded_over_healthy": {
            "stale_index": step_us["stale_index"] / ok_us,
            "uniform_fallback": step_us["uniform_fallback"] / ok_us,
        },
        "recovery": {
            "injected_cycles": 2, "steps_run": rec_steps,
            "degraded_at_step": degraded_at,
            "recovered_at_step": recovered_at,
            "latency_steps": latency, "recovered": recovered,
        },
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "robustness.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_multihost(quick: bool = False):
    """Multi-host deployment cost + reform latency (one table).

    Two gated quantities for the elastic multi-process story:
      * 2-process step time — MEAN Trainer-step wall time of a real
        2-process ``jax.distributed`` CPU run (each process owns one
        corpus shard; barrier + parameter average every ``sync_every``
        steps) vs the SAME 2-shard problem in one process.  The mean —
        not p10 — because the sync barrier fires every ``sync_every``
        steps and its amortised cost IS the deployment tax being gated.
      * reform-time-to-first-step — in a host-kill drill, wall time
        from the survivor starting its reform (newest-verified
        checkpoint restore + pipeline rebuild on the surviving shard
        count) to completing its first post-reform trainer step.

    Both processes time the identical deterministic worker stack
    (``repro.dist.multihost_worker``), so the 2-proc/1-proc ratio is a
    same-stack comparison; per-step stamps come from the worker's
    result JSON (first ``warmup`` deltas dropped — jit compile).
    """
    import socket
    import subprocess
    import sys
    import tempfile

    from repro.dist.multihost_worker import (
        LR, PARAM_KEY_SEED, build_pipeline, model_cfg)
    from repro.testing import ProcKill

    if jax.default_backend() != "cpu":
        # the two worker processes run on the CPU (one chip admits one
        # process), so the in-process baseline must run there too
        raise RuntimeError("tab_multihost compares CPU processes only: "
                           "run it with JAX_PLATFORMS=cpu")
    steps = 20 if quick else 40
    warmup = 4
    sync_every = 5
    src = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                       "..", "src"))

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def run_pair(d, n_steps, ckpt_every, rank1_extra=()):
        coord = f"127.0.0.1:{free_port()}"
        common = [sys.executable, "-m", "repro.dist.multihost_worker",
                  "--nprocs", "2", "--coordinator", coord,
                  "--ckpt-dir", os.path.join(d, "ckpt"),
                  "--steps", str(n_steps),
                  "--sync-every", str(sync_every),
                  "--ckpt-every", str(ckpt_every)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        procs = [subprocess.Popen(
            common + ["--rank", str(r),
                      "--result", os.path.join(d, f"r{r}.json")]
            + (list(rank1_extra) if r == 1 else []),
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL) for r in (0, 1)]
        rcs = [p.wait(timeout=600) for p in procs]
        path = os.path.join(d, "r0.json")
        r0 = json.load(open(path)) if os.path.exists(path) else None
        return rcs, r0

    # -- 2-process step time (clean run, checkpointing off) -----------
    with tempfile.TemporaryDirectory() as d:
        rcs, r0 = run_pair(d, steps + warmup, ckpt_every=10 ** 9)
    if rcs != [0, 0] or r0 is None:
        raise RuntimeError(
            f"tab_multihost clean 2-process run failed: exit codes {rcs}")
    deltas = np.diff(r0["timings"]["step_stamps"])[warmup - 1:]
    us_2p = float(np.mean(deltas)) * 1e6

    # -- single-process 2-shard baseline (same stack, in process) -----
    cfg = model_cfg()
    params = init_params(jax.random.PRNGKey(PARAM_KEY_SEED), cfg)
    pipe = build_pipeline(params, n_shards=2)
    tr = Trainer(cfg, params, Adam(lr=LR),
                 tcfg=TrainerConfig(log_every=10_000), sampler=pipe)
    tr.run(warmup)
    dts = []
    for _ in range(steps):
        t0 = time.perf_counter()
        tr.run(1)
        dts.append(time.perf_counter() - t0)
    tr.finalize()
    us_1p = float(np.mean(dts)) * 1e6

    # -- reform latency (host-kill drill) -----------------------------
    with tempfile.TemporaryDirectory() as d:
        rcs, r0k = run_pair(d, 25, ckpt_every=10,
                            rank1_extra=("--kill-at", "12"))
    if rcs != [0, ProcKill.EXIT_CODE] or r0k is None:
        raise RuntimeError(
            f"tab_multihost kill drill failed: exit codes {rcs}")
    reformed = r0k["cluster"]["state"] == "reformed"
    reform_s = r0k["timings"].get("reform_to_first_step_s")

    ratio = us_2p / max(us_1p, 1e-9)
    _row("tab_multihost_one_proc", us_1p, "cpu, 2 shards, one process")
    _row("tab_multihost_two_proc", us_2p,
         f"cpu, {ratio:.2f}x one-process")
    _row("tab_multihost_reform", 0.0,
         f"{reform_s:.2f}s to first post-reform step" if reformed
         and reform_s is not None else "NOT REFORMED")
    out = {
        "backend": "cpu",
        "quick": quick, "batch": 16, "n_corpus": 256,
        "steps_timed": steps, "warmup": warmup, "nprocs": 2,
        "sync_every": sync_every,
        "step_us": {"one_proc_two_shard": us_1p, "two_proc": us_2p,
                    "two_proc_over_one_proc": ratio},
        "reform": {
            "reformed": reformed,
            "restore_step": r0k.get("restore_step"),
            "reform_shards": r0k.get("reform_shards"),
            "to_first_step_s": reform_s,
        },
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "multihost.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_optimizers(quick: bool = False):
    """Adaptive optimisers under LGD + multi-probe querying (one table).

    Three gated quantities (see docs/BENCHMARKS.md):
      * per-optimizer END-TO-END step wall time, uniform vs LGD, on the
        tiny-LM Trainer path (LGD pipeline runs multiprobe=2) — the
        paper's claim that LGD "reduces the running time of all
        existing gradient descent algorithms ... including Adam,
        Ada-grad" requires the sampler overhead to stay bounded under
        every update rule, not just SGD.  Gate: LGD-Adam <= 1.3x
        uniform-Adam (quick CPU mode).
      * per-optimizer ESTIMATOR variance, Tr Cov of the 1-sample LGD
        estimator vs uniform SGD at the theta reached by a short run of
        that optimiser (Lemma-1 pareto regime — early training, where
        Thm 2's win is provable).  Gate: LGD-Adam variance ratio < 1.
      * multi-probe FALLBACK rate on a skewed corpus (tight cluster,
        partially-aligned query, K >> log2 N so exact buckets are often
        empty): single-probe vs multiprobe=2 under identical keys.
        Gate: multi < single, strictly.

    Optimiser timings are interleaved in one loop (uniform step, LGD
    step, next optimiser, repeat) with 10th-percentile stats, the same
    drift discipline as ``tab_train_step``.
    """
    from repro.optim import SGD as _SGD

    opts = {
        "momentum": _SGD(lr=3e-2, momentum=0.9),
        "adagrad": AdaGrad(lr=3e-2),
        "adam": Adam(lr=3e-3),
    }

    # --- end-to-end LM step timing per optimiser ---------------------------
    cfg = ModelConfig(
        name="lm-optim", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, chunk=16, loss_chunk=64,
        dtype="float32", rope_theta=10000.0)
    n_corpus, batch = (512, 16) if quick else (2048, 32)
    steps = 12 if quick else 32
    multiprobe = 2
    corpus = make_token_corpus(17, n_corpus, 24, cfg.vocab, hard_frac=0.12)

    def make_pair(opt):
        params_u = init_params(KEY, cfg)
        tr_uni = Trainer(cfg, params_u, opt,
                         batches=uniform_batches(corpus, batch, seed=22),
                         tcfg=TrainerConfig(log_every=10_000, donate=False))
        params_l = init_params(KEY, cfg)
        sampler = LSHSampledPipeline(
            jax.random.PRNGKey(21), corpus.tokens,
            mean_pool_feature_fn(cfg), lm_head_query_fn(),
            LSHPipelineConfig(k=5, l=10, minibatch=batch,
                              refresh_every=max(steps // 2, 8),
                              refresh_async=True, multiprobe=multiprobe),
            params=params_l)
        tr_lgd = Trainer(cfg, params_l, opt,
                         tcfg=TrainerConfig(log_every=10_000),
                         sampler=sampler)
        return tr_uni, tr_lgd, sampler

    pairs = {name: make_pair(opt) for name, opt in opts.items()}
    for tr_uni, tr_lgd, _ in pairs.values():        # warm up jit + caches
        tr_uni.run(3)
        tr_lgd.run(3)
    dts = {name: ([], []) for name in opts}
    for _ in range(steps):
        for name, (tr_uni, tr_lgd, _) in pairs.items():
            t0 = time.perf_counter()
            tr_uni.run(1)
            dts[name][0].append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            tr_lgd.run(1)
            dts[name][1].append(time.perf_counter() - t0)

    step_out = {}
    for name, (du, dl) in dts.items():
        us_uni = float(np.percentile(du, 10)) * 1e6
        us_lgd = float(np.percentile(dl, 10)) * 1e6
        step_out[name] = {"uniform": us_uni, "lgd": us_lgd,
                          "overhead": us_lgd / max(us_uni, 1e-9)}
        _row(f"tab_optim_step[{name}]", us_lgd,
             f"{us_lgd / max(us_uni, 1e-9):.2f}x uniform")
    for tr_uni, tr_lgd, sampler in pairs.values():
        tr_uni.finalize()
        tr_lgd.finalize()

    # --- estimator variance per optimiser (Lemma-1 pareto regime) ----------
    # alpha=1.2 pareto residuals + minibatch-mean estimators: heavy
    # tails give LGD its provable variance win (early training), and
    # measuring Var of the m=16 minibatch mean (not single samples)
    # keeps the empirical Tr Cov stable enough to gate.
    kx, ky, kt, kn = jax.random.split(jax.random.PRNGKey(4), 4)
    n_lin, d_lin = 1200, 16
    trials = 400 if quick else 1000
    theta_steps = 10          # early training: gradient norms still skewed
    m_var = 16
    x = jax.random.normal(kx, (n_lin, d_lin))
    noise = jax.random.pareto(kn, 1.2, (n_lin,)) * \
        jax.random.rademacher(ky, (n_lin,)).astype(jnp.float32) * 0.3
    y = x @ jax.random.normal(kt, (d_lin,)) + noise
    xt, yt, x_aug = preprocess_regression(x, y)
    p_lin = LSHParams(k=5, l=100, dim=d_lin + 1, family="quadratic")
    index = _build_index(jax.random.PRNGKey(10), x_aug, p_lin)
    prob = LGDProblem(kind="regression", lsh=p_lin, minibatch=m_var)

    var_out = {}
    for oi, (name, opt) in enumerate(opts.items()):
        # theta reached by a short uniform run of THIS optimiser: the
        # estimator comparison is at matched params, early training.
        state = lgd_init(jax.random.PRNGKey(12), prob, x, y, opt)[0]
        for i in range(theta_steps):
            state, _ = sgd_step(jax.random.fold_in(KEY, i), state, xt, yt,
                                prob, opt)
        theta = state.theta
        q = regression_query(theta)
        keys = jax.random.split(jax.random.fold_in(KEY, 1000 + oi), trials)

        def one_lgd(k):
            r = S.sample(k, index, x_aug, q, p_lin, m=m_var)
            return E.lgd_gradient(squared_loss_grad, theta, xt[r.indices],
                                  yt[r.indices], r, n_lin)

        def one_sgd(k):
            idx = jax.random.randint(k, (m_var,), 0, n_lin)
            g = jax.vmap(lambda i: squared_loss_grad(theta, xt[i], yt[i])
                         )(idx)
            return jnp.mean(g, axis=0)

        v_lgd = float(E.empirical_estimator_covariance_trace(
            jax.lax.map(one_lgd, keys)))
        v_sgd = float(E.empirical_estimator_covariance_trace(
            jax.lax.map(one_sgd, keys)))
        var_out[name] = {"lgd": v_lgd, "uniform": v_sgd,
                         "ratio": v_lgd / max(v_sgd, 1e-30)}
        _row(f"tab_optim_var[{name}]", 0.0,
             f"{v_lgd / max(v_sgd, 1e-30):.3f}")

    # --- multi-probe fallback on a skewed corpus ---------------------------
    # tight cluster + partially-aligned perturbed queries + K >> log2 N:
    # exact buckets are often empty, so single-probe falls back to
    # uniform ~50% of the time; a flip-1 Hamming walk (multiprobe=8 of
    # the K=16 bits) resolves most of those to corrected near-bucket
    # samples.  Averaged over a 64-query batch so the rate is smooth
    # (one fixed query only exposes the table-draw randomness).
    n_sk, d_sk, k_sk, l_sk, mp_sk = 256, 24, 16, 3, 8
    c = jax.random.normal(jax.random.PRNGKey(9), (d_sk,))
    x_sk = c[None] + 0.55 * jax.random.normal(jax.random.PRNGKey(30),
                                              (n_sk, d_sk))
    x_sk = x_sk / jnp.linalg.norm(x_sk, axis=-1, keepdims=True)
    p_sk = LSHParams(k=k_sk, l=l_sk, dim=d_sk, family="dense")
    idx_sk = _build_index(jax.random.PRNGKey(1), x_sk, p_sk)
    qs = c[None] + 0.9 * jax.random.normal(jax.random.PRNGKey(11),
                                           (64, d_sk))
    qs = qs / jnp.linalg.norm(qs, axis=-1, keepdims=True)
    fb_m = 64 if quick else 256
    fb = {}
    for tag, mp in (("single", 0), ("multi", mp_sk)):
        r = S.sample_batched(jax.random.PRNGKey(4), idx_sk, x_sk, qs, p_sk,
                             m=fb_m, multiprobe=mp)
        fb[tag] = float(jnp.mean(r.fallback))
    _row("tab_optim_fallback", 0.0,
         f"single {fb['single']:.3f} -> multi {fb['multi']:.3f}")

    out = {
        "backend": jax.default_backend(),
        "quick": quick, "batch": batch, "n_corpus": n_corpus,
        "steps_timed": steps, "multiprobe": multiprobe,
        "optimizers": {name: {"step_us": step_out[name],
                              "estimator_variance": var_out[name]}
                       for name in opts},
        "fallback": {"single": fb["single"], "multi": fb["multi"],
                     "multiprobe": mp_sk, "k": k_sk, "l": l_sk,
                     "n_points": n_sk, "query_batch": 64, "m": fb_m},
    }
    os.makedirs(RESULTS, exist_ok=True)
    # optimizers.json is the CI regression-gate baseline (quick mode);
    # BENCH_optimizers.json keeps the full-mode trajectory record.
    fname = "optimizers.json" if quick else "BENCH_optimizers.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


def tab_families(quick: bool = False):
    """SRP vs MIPS (asymmetric Simple-LSH) on an UN-normalised corpus.

    Two gated quantities (see docs/BENCHMARKS.md):

    * per-draw SAMPLING cost, SRP index vs MIPS index over the same
      un-normalised corpus, interleaved in one loop with 10th-percentile
      stats (the drift discipline of ``tab_sampling_cost``).  The MIPS
      family is linear SRP in aug_dim = d+1 dimensions — same fused
      kernels, one extra column — so its step must stay within
      ``--families-step-cap`` (default 1.15x) of SRP, same run.

    * ESTIMATOR variance in the calibrated skewed regime: un-normalised
      rows (x norms 2.7–3.3, never row-scaled), a 10% outlier cluster
      with capped heavy residuals, theta = 0 (early training — Lemma 1),
      K=3 / L=100.  The calibration keeps the augmented geometry inside
      Simple-LSH's exact zone: residual outliers bounded by ~the x
      norms, so no point collapses to the augmentation pole, buckets
      stay populated (l = 1, where Algorithm 1's probability formula is
      exact) and K stays small so Theorem 2's bucket-size noise does
      not swallow the collision tilt (docs/ARCHITECTURE.md documents
      this boundary).  Measured as Tr Cov of the single-sample
      importance-weighted estimator over ``draws`` draws, averaged over
      8 index builds, vs uniform sampling on the same corpus.  Gate:
      MIPS/uniform < ``--families-var-cap`` (default 1.0).  Symmetric
      dense SRP on the row-normalised version of the same corpus is
      recorded for the table (informational).

    * HEAVY-TAIL calibration (``heavy_tail`` block): log-normal
      exp(0.8·z) norms — the documented regime where the single global
      Simple-LSH scale miscalibrates (docs/ARCHITECTURE.md).  Plain
      ``mips`` vs norm-ranged ``mips_banded``: E[1/(p·N)] over index
      builds, and Tr Cov of the single-sample importance-weighted
      estimator on a heavy-tailed regression.  check_regression.py
      gates the FRESH run absolutely: banded E[1/(p·N)] within
      ``--banded-calibration`` (default 0.1) of 1, and banded Tr Cov
      strictly below plain mips on the same corpus.
    """
    from repro.core import get_family
    from repro.core.lgd import preprocess_regression_mips

    n, d = (2000, 32) if quick else (4000, 32)
    iters = 150 if quick else 300
    draws = 10_000 if quick else 30_000
    builds = 8
    k_lsh, l_lsh = 3, 100

    # un-normalised corpus: spread directions, 2.7-3.3 norms, 10%
    # outlier cluster with a tight capped heavy tail (see docstring)
    kx, kn, knn, kb = jax.random.split(jax.random.PRNGKey(33), 4)
    dirs = jax.random.normal(kx, (n, d))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    x = dirs * jax.random.uniform(kn, (n, 1), minval=2.7, maxval=3.3)
    mask = jax.random.bernoulli(kb, 0.1, (n,)).astype(jnp.float32)
    y = -mask * jnp.minimum(
        1.2 + 0.2 * jax.random.pareto(knn, 2.0, (n,)), 1.8)

    fam_mips = get_family("mips")
    xt_m, yt_m, xa_mips = preprocess_regression_mips(x, y, fam_mips)
    # symmetric SRP on the SAME corpus: the paper's preprocessing
    # (row-normalised x, hash [x, y])
    xt_s, yt_s, xa_srp = preprocess_regression(x, y)

    p_srp = LSHParams(k=k_lsh, l=l_lsh, dim=d + 1, family="dense")
    p_mips = LSHParams(k=k_lsh, l=l_lsh, dim=d + 2, family="mips")
    idx_srp = _build_index(jax.random.PRNGKey(34), xa_srp, p_srp)
    idx_mips = _build_index(jax.random.PRNGKey(34), xa_mips, p_mips)

    theta = jnp.zeros(d)                     # early training (Lemma 1)
    q_srp = regression_query(theta)
    q_mips = fam_mips.augment_query(regression_query(theta))

    # --- interleaved per-draw sampling cost -------------------------------
    srp_fn = lambda k: S.sample(k, idx_srp, xa_srp, q_srp, p_srp,   # noqa: E731
                                m=1).indices
    mips_fn = lambda k: S.sample(k, idx_mips, xa_mips, q_mips,      # noqa: E731
                                 p_mips, m=1).indices
    jax.block_until_ready(srp_fn(KEY))
    jax.block_until_ready(mips_fn(KEY))
    dt_s, dt_m = [], []
    for i in range(iters):
        kk = jax.random.fold_in(KEY, i)
        t0 = time.perf_counter()
        jax.block_until_ready(srp_fn(kk))
        dt_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(mips_fn(kk))
        dt_m.append(time.perf_counter() - t0)
    us_srp = float(np.percentile(dt_s, 10)) * 1e6
    us_mips = float(np.percentile(dt_m, 10)) * 1e6

    # --- estimator variance over draws, averaged over index builds --------
    def var_over_builds(x_aug, qv, params, xt, yt):
        def per_build(bk):
            kb_, ks = jax.random.split(bk)
            index = _build_index(kb_, x_aug, params)
            r = S.sample(ks, index, x_aug, qv, params, m=draws)
            w = 1.0 / (r.probs * n)
            g = jax.vmap(lambda i, wi: squared_loss_grad(
                theta, xt[i], yt[i]) * wi)(r.indices, w)
            return E.empirical_estimator_covariance_trace(g)
        vs = jax.lax.map(per_build,
                         jax.random.split(jax.random.PRNGKey(35), builds))
        return float(jnp.mean(vs))

    def one_uni(kk):
        i = jax.random.randint(kk, (), 0, n)
        return squared_loss_grad(theta, xt_m[i], yt_m[i])

    v_uni = float(E.empirical_estimator_covariance_trace(jax.lax.map(
        one_uni, jax.random.split(jax.random.PRNGKey(36), draws))))
    v_mips = var_over_builds(xa_mips, q_mips, p_mips, xt_m, yt_m)
    # SRP comparison on ITS preprocessing, vs uniform on the same
    def one_uni_s(kk):
        i = jax.random.randint(kk, (), 0, n)
        return squared_loss_grad(theta, xt_s[i], yt_s[i])
    v_uni_s = float(E.empirical_estimator_covariance_trace(jax.lax.map(
        one_uni_s, jax.random.split(jax.random.PRNGKey(36), draws))))
    v_srp = var_over_builds(xa_srp, q_srp, p_srp, xt_s, yt_s)

    var_mips = {"lgd": v_mips, "uniform": v_uni,
                "ratio": v_mips / max(v_uni, 1e-30)}
    var_srp = {"lgd": v_srp, "uniform": v_uni_s,
               "ratio": v_srp / max(v_uni_s, 1e-30)}

    # --- heavy-tail calibration: plain mips vs norm-ranged banded ---------
    # (see docstring; same K/L as the variance block, log-normal norms)
    khx, khn, khq = jax.random.split(jax.random.PRNGKey(8), 3)
    dirs_h = jax.random.normal(khx, (n, d))
    dirs_h = dirs_h / jnp.linalg.norm(dirs_h, axis=-1, keepdims=True)
    xh = dirs_h * jnp.exp(0.8 * jax.random.normal(khn, (n, 1)))
    qh_raw = jax.random.normal(khq, (d,))
    kht, khe = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(8), 1))
    yh = xh @ jax.random.normal(kht, (d,)) + \
        0.1 * jax.random.normal(khe, (n,))

    def calib_heavy(fam_name):
        fam = get_family(fam_name)
        xa = fam.augment_data(xh)
        qa = fam.augment_query(qh_raw)
        pp = LSHParams(k=k_lsh, l=l_lsh, dim=xa.shape[-1], family=fam_name)

        def per_build(bk):
            kb_, ks = jax.random.split(bk)
            index = _build_index(kb_, xa, pp)
            r = S.sample(ks, index, xa, qa, pp, m=2000)
            return jnp.mean(1.0 / (r.probs * n))

        ms = jax.lax.map(per_build,
                         jax.random.split(jax.random.PRNGKey(11), builds))
        return float(jnp.mean(ms)), float(jnp.std(ms))

    def trcov_heavy(fam_name):
        fam = get_family(fam_name)
        xt_h, yt_h, xa_h = preprocess_regression_mips(xh, yh, fam)
        pp = LSHParams(k=k_lsh, l=l_lsh, dim=xa_h.shape[-1],
                       family=fam_name)
        qv = fam.augment_query(regression_query(theta))
        return var_over_builds(xa_h, qv, pp, xt_h, yt_h)

    inv_plain, invsd_plain = calib_heavy("mips")
    inv_band, invsd_band = calib_heavy("mips_banded")
    tr_plain_h = trcov_heavy("mips")
    tr_band_h = trcov_heavy("mips_banded")

    _row("tab_families_step[srp]", us_srp, "baseline")
    _row("tab_families_step[mips]", us_mips,
         f"{us_mips / max(us_srp, 1e-9):.3f}x srp")
    _row("tab_families_var[mips]", 0.0, f"{var_mips['ratio']:.3f}")
    _row("tab_families_var[srp]", 0.0, f"{var_srp['ratio']:.3f}")
    _row("tab_families_invp[mips]", 0.0, f"{inv_plain:.3f}")
    _row("tab_families_invp[banded]", 0.0, f"{inv_band:.3f}")
    _row("tab_families_trcov[banded/mips]", 0.0,
         f"{tr_band_h / max(tr_plain_h, 1e-30):.3f}")

    out = {
        "backend": jax.default_backend(),
        "quick": quick, "n_points": n, "d": d, "k": k_lsh, "l": l_lsh,
        "draws": draws, "builds": builds,
        "step_us": {"srp": us_srp, "mips": us_mips,
                    "mips_vs_srp": us_mips / max(us_srp, 1e-9)},
        "estimator_variance": {"mips": var_mips, "srp": var_srp},
        "heavy_tail": {
            "sigma": 0.8,
            "inv_p": {"mips": inv_plain, "mips_banded": inv_band},
            "inv_p_sd": {"mips": invsd_plain, "mips_banded": invsd_band},
            "trcov": {"mips": tr_plain_h, "mips_banded": tr_band_h,
                      "banded_vs_plain":
                          tr_band_h / max(tr_plain_h, 1e-30)},
        },
    }
    os.makedirs(RESULTS, exist_ok=True)
    # families.json is the CI regression-gate baseline (quick mode);
    # BENCH_families.json keeps the full-mode trajectory record.
    fname = "families.json" if quick else "BENCH_families.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


def thm2_variance():
    # Lemma-1 regime (calibrated in tests/test_estimator.py): pareto
    # alpha=1.5 residuals, theta=0 (early training).
    kx, ky, kt, kn = jax.random.split(jax.random.PRNGKey(4), 4)
    n, d = 2000, 16
    x = jax.random.normal(kx, (n, d))
    noise = jax.random.pareto(kn, 1.5, (n,)) * \
        jax.random.rademacher(ky, (n,)).astype(jnp.float32) * 0.1
    y = x @ jax.random.normal(kt, (d,)) + noise
    xt, yt, x_aug = preprocess_regression(x, y)
    p = LSHParams(k=5, l=100, dim=d + 1, family="quadratic")
    index = _build_index(jax.random.PRNGKey(10), x_aug, p)
    theta = jnp.zeros(d)
    q = regression_query(theta)
    keys = jax.random.split(jax.random.PRNGKey(11), 1500)

    def one(k):
        r = S.sample(k, index, x_aug, q, p, m=1)
        return E.lgd_gradient(squared_loss_grad, theta, xt[r.indices],
                              yt[r.indices], r, xt.shape[0])

    def one_sgd(k):
        i = jax.random.randint(k, (), 0, xt.shape[0])
        return squared_loss_grad(theta, xt[i], yt[i])

    t0 = time.perf_counter()
    v_lgd = float(E.empirical_estimator_covariance_trace(
        jax.lax.map(one, keys)))
    us = (time.perf_counter() - t0) / 1500 * 1e6
    v_sgd = float(E.empirical_estimator_covariance_trace(
        jax.lax.map(one_sgd, keys)))
    _row("thm2_variance_ratio", us, f"{v_lgd / v_sgd:.3f}")
    return dict(var_lgd=v_lgd, var_sgd=v_sgd)


def tab_softmax(quick: bool = False):
    """LSH-sampled softmax head vs the full-vocab O(V) head.

    Four gated quantities (benchmarks/check_regression.py):

      train_ratio       sampled-head train step (loss+grad, sampling
                        INSIDE the jitted step) / full-vocab head step,
                        same model/batch — must be < 1 at the
                        benchmarked V (the whole point of the head).
      proj_decode_ratio decode tokens/s of the shortlist head over the
                        full matmul head at V = SHAPES['vocab_large']
                        (131k), PROJECTED from the roofline byte model
                        (HBM-bound regime: full head streams d*V*4
                        bytes/token; the shortlist streams projections
                        + J*L*c candidate columns) — must be >= 1.
                        The measured head-only ratio at the benchmarked
                        (CPU-sized) V is reported unprojected alongside.
      zhat_rel_err      |E[Zhat]/Z - 1| measured over index builds on
                        the live head rows — the unbiasedness identity
                        at bench scale.
      shortlist_recall  recall@1 of the probe shortlist on planted
                        near-duplicate queries (the trained-head,
                        argmax-has-margin regime).

    TWO REGIMES, TWO CONFIGS.  The sampling estimator needs POPULATED
    buckets (occupancy >> 1) for Algorithm 1's probability law to be
    exact — plain ``mips``, coarse k.  The decode shortlist needs the
    opposite: fine buckets so c slots hold a bucket, plus norm-ranging
    (``mips_banded``) because one global Simple-LSH scale caps an
    exact-match query's per-table collision at (||x||/M)-cosine —
    measured recall 0.49 single-index vs 0.98 banded on the same head.
    """
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import HBM_BW
    from repro.models import (
        LMHeadIndex, SampledSoftmaxConfig, make_sampled_loss,
    )
    from repro.models.layers import rms_norm
    from repro.models.sampled_softmax import (
        head_lsh_params, shortlist_candidates, shortlist_logits,
    )
    from repro.core.families import get_family

    vocab = 8192 if quick else 32768
    cfg = ModelConfig(
        name="lm-softmax", n_layers=1, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=vocab, chunk=32, loss_chunk=256,
        dtype="float32", rope_theta=10000.0)
    # training/estimator config: coarse k keeps mean bucket occupancy
    # V/2^k ~ 32 (the populated-bucket regime where the probability law
    # is calibrated — tests/test_sampled_softmax.py)
    scfg = SampledSoftmaxConfig(k=vocab.bit_length() - 6, l=8,
                                n_samples=32, multiprobe=2,
                                drift_sample=0.0)
    # decode-shortlist config: norm-ranged bands + fine buckets (each
    # band's occupancy ~ shortlist_per_table so c slots cover a bucket)
    dcfg = SampledSoftmaxConfig(family="mips_banded", k=10, l=8,
                                multiprobe=2, shortlist_per_table=8,
                                drift_sample=0.0)
    b, s = 8, 32
    iters = 6 if quick else 12
    params = init_params(KEY, cfg)
    head = LMHeadIndex(params, cfg, scfg)
    toks = jax.random.randint(jax.random.fold_in(KEY, 1), (b, s + 1), 0,
                              vocab)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    def _time(fn, *a):
        fn(*a)                                   # compile off the clock
        jax.block_until_ready(fn(*a)[0])
        dts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a)[0])
            dts.append(time.perf_counter() - t0)
        return float(np.median(dts)) * 1e6

    # -- train step: loss + grad, full head vs sampled head ------------
    full_step = jax.jit(jax.value_and_grad(
        lambda prm, bt: lm_loss(prm, cfg, bt)))
    us_full = _time(full_step, params, batch)
    sampled_loss = make_sampled_loss(cfg, scfg)
    lsh_step = jax.jit(jax.value_and_grad(sampled_loss))
    us_lsh = _time(lsh_step, params, head.inject(batch, step=0))
    train_ratio = us_lsh / max(us_full, 1e-9)

    # -- decode head: full matmul argmax vs probe+shortlist argmax -----
    dfam = get_family(dcfg.family)
    dlsh = head_lsh_params(cfg, dcfg)
    dhead = LMHeadIndex(params, cfg, dcfg)
    nq = 64
    h = jax.random.normal(jax.random.fold_in(KEY, 2), (nq, cfg.d_model))
    q = rms_norm(params["embed_group"]["final_norm"], h,
                 cfg.norm_eps).astype(jnp.float32)

    def full_head(prm, qq):
        return jnp.argmax(qq @ prm["embed_group"]["lm_head"], -1), ()
    us_full_dec = _time(jax.jit(full_head), params, q)

    def lsh_head(prm, qq, idx):
        ids, valid = shortlist_candidates(idx, dfam.augment_query(qq),
                                          dlsh, dcfg)
        lg = shortlist_logits(prm["embed_group"]["lm_head"], qq, ids,
                              valid)
        best = jnp.argmax(lg, -1)
        return jnp.take_along_axis(ids, best[:, None], 1)[:, 0], ()
    us_lsh_dec = _time(jax.jit(lsh_head), params, q, dhead.index)
    decode_ratio_measured = (us_full_dec / nq) / max(us_lsh_dec / nq,
                                                     1e-9)

    # -- roofline projection to production V (vocab_large) -------------
    v_big = SHAPES["vocab_large"].vocab
    d = cfg.d_model
    aug = dfam.aug_dim(d)
    n_cand = (dfam.num_bands() * (1 + dcfg.multiprobe) * dcfg.l
              * dcfg.shortlist_per_table)
    bytes_full = 4.0 * d * v_big                  # stream the head
    bytes_lsh = (4.0 * aug * dcfg.k * dcfg.l     # projections
                 + 4.0 * dcfg.l * 64             # sorted-code probes
                 + 4.0 * n_cand * (d + 1))       # candidate columns+ids
    proj_full_tok_s = HBM_BW / bytes_full
    proj_lsh_tok_s = HBM_BW / bytes_lsh
    proj_decode_ratio = proj_lsh_tok_s / proj_full_tok_s

    # -- estimator quality at bench scale -------------------------------
    rows = params["embed_group"]["lm_head"].astype(jnp.float32).T
    hq = jax.random.normal(jax.random.fold_in(KEY, 3), (32, d)) * 0.5
    logits_all = hq @ rows.T
    z = jnp.sum(jnp.exp(logits_all), -1)
    rels = []
    for t in range(4 if quick else 8):
        hb = LMHeadIndex(params, cfg, dataclasses.replace(scfg, seed=t + 1))
        res = S.sample_batched(
            jax.random.fold_in(KEY, 100 + t), hb.index, hb.x_aug,
            get_family(scfg.family).augment_query(hq),
            head_lsh_params(cfg, dataclasses.replace(scfg, seed=t + 1)),
            m=64, multiprobe=scfg.multiprobe)
        l_neg = jnp.take_along_axis(logits_all, res.indices, 1)
        rels.append(np.asarray(
            jnp.mean(jnp.exp(l_neg) / res.probs, -1) / z))
    zhat_rel_err = float(abs(np.mean(np.stack(rels)) - 1.0))

    # -- shortlist recall on planted winners ----------------------------
    winners = jax.random.randint(jax.random.fold_in(KEY, 4), (128,), 0,
                                 vocab)
    qr = rows[winners] + 0.05 * jnp.std(rows) * jax.random.normal(
        jax.random.fold_in(KEY, 5), (128, d))
    ids, valid = shortlist_candidates(dhead.index, dfam.augment_query(qr),
                                      dlsh, dcfg)
    lg = shortlist_logits(params["embed_group"]["lm_head"], qr, ids,
                          valid)
    got = jnp.take_along_axis(ids, jnp.argmax(lg, -1)[:, None], 1)[:, 0]
    true = jnp.argmax(qr @ rows.T, -1)
    recall = float(jnp.mean((got == true).astype(jnp.float32)))

    _row("tab_softmax_full_step", us_full, "baseline")
    _row("tab_softmax_lsh_step", us_lsh,
         f"{train_ratio:.3f}x of full head")
    _row("tab_softmax_full_decode_head", us_full_dec / nq, "us/token")
    _row("tab_softmax_lsh_decode_head", us_lsh_dec / nq,
         f"measured {decode_ratio_measured:.2f}x; projected "
         f"{proj_decode_ratio:.0f}x at V={v_big}")
    _row("tab_softmax_zhat_rel_err", 0.0, f"{zhat_rel_err:.4f}")
    _row("tab_softmax_shortlist_recall", 0.0, f"{recall:.3f}")

    out = {
        "backend": jax.default_backend(),
        "quick": quick, "vocab": vocab, "d_model": d,
        "k": scfg.k, "l": scfg.l, "multiprobe": scfg.multiprobe,
        "n_samples": scfg.n_samples,
        "decode_family": dcfg.family, "decode_k": dcfg.k,
        "decode_l": dcfg.l,
        "shortlist_per_table": dcfg.shortlist_per_table,
        "n_candidates": n_cand,
        "full_step_us": us_full,
        "lsh_step_us": us_lsh,
        "train_ratio": train_ratio,
        "full_decode_head_us_per_token": us_full_dec / nq,
        "lsh_decode_head_us_per_token": us_lsh_dec / nq,
        "decode_ratio_measured": decode_ratio_measured,
        "proj_vocab": v_big,
        "proj_tokens_s_full": proj_full_tok_s,
        "proj_tokens_s_lsh": proj_lsh_tok_s,
        "proj_decode_ratio": proj_decode_ratio,
        "zhat_rel_err": zhat_rel_err,
        "shortlist_recall": recall,
    }
    os.makedirs(RESULTS, exist_ok=True)
    fname = "softmax.json" if quick else "BENCH_softmax.json"
    with open(os.path.join(RESULTS, fname), "w") as f:
        json.dump(out, f, indent=2)
    return out


TABLES = {
    "fig9_sample_quality": lambda quick: fig9_sample_quality(),
    "fig10_convergence": lambda quick: fig10_convergence(),
    "fig12_adagrad": lambda quick: fig12_adagrad(),
    "tab_sampling_cost": tab_sampling_cost,
    "tab_refresh_cost": tab_refresh_cost,
    "tab_streaming": tab_streaming,
    "fig5_lm_epochwise": lambda quick: fig5_lm_epochwise(),
    "tab_train_step": tab_train_step,
    "tab_robustness": tab_robustness,
    "tab_multihost": tab_multihost,
    "tab_optimizers": tab_optimizers,
    "tab_families": tab_families,
    "tab_softmax": tab_softmax,
    "thm2_variance": lambda quick: thm2_variance(),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("tables", nargs="*", choices=list(TABLES) + [[]],
                    help="tables to run (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized problems/iterations")
    args = ap.parse_args()
    names = args.tables or list(TABLES)
    enable_compile_cache()

    os.makedirs(RESULTS, exist_ok=True)
    print("name,us_per_call,derived")
    quick_aware = {"tab_sampling_cost", "tab_refresh_cost",
                   "tab_streaming", "tab_train_step", "tab_robustness",
                   "tab_multihost", "tab_optimizers", "tab_families",
                   "tab_softmax"}
    if args.quick:
        ignored = [n for n in names if n not in quick_aware]
        if ignored:
            print(f"# note: --quick has no effect on {ignored}; these "
                  "run at full size", flush=True)
    all_out = {}
    for name in names:
        all_out[name] = TABLES[name](args.quick)
    if set(names) == set(TABLES):
        with open(os.path.join(RESULTS, "benchmarks.json"), "w") as f:
            json.dump(all_out, f, indent=2)


if __name__ == "__main__":
    main()

"""End-to-end driver: train a decoder LM with the LGD-sampled data pipeline.

Presets:
  demo  (default)  ~3M params, a few hundred steps on CPU in minutes —
                   compares the LSH-sampled pipeline against uniform.
  100m             ~100M-param config (d=768, 12L) for a real host/TPU;
                   identical code path, bigger numbers.

Sampler (``--sampler {uniform,lgd}``):
  uniform          i.i.d. uniform batches (the SGD baseline).
  lgd              the paper's LSH-sampled adaptive batches: example
                   features (pooled last-layer reps) are hashed into
                   per-shard LSH indexes; each step queries with the
                   output-layer direction and draws Algorithm-1 samples,
                   de-biased by 1/(p_i N) importance weights inside the
                   jitted loss.  Batches are DEVICE-RESIDENT: the token
                   store is uploaded once and each draw is a single
                   compiled sample->gather->weight call — watch the
                   ``sampler`` fraction in the progress line sit near
                   zero.  The periodic index refresh runs on a host
                   thread, double-buffered, so re-hashing overlaps
                   device compute.

Refresh mode (``--refresh-mode {full,delta}``):
  full             re-embed + re-hash the whole corpus every
                   ``refresh_every`` steps.
  delta            re-embed/re-hash only the examples VISITED since the
                   last refresh plus a drift-sampled remainder, merged
                   into the sorted index through the previous order —
                   refresh cost scales with drift, not corpus size
                   (benchmarks/run.py tab_refresh_cost quantifies it).

Sharded-index contract (``--shards S``): the corpus is split into S
contiguous equal shards (one per data-parallel group at scale — S
defaults to 1 on a single host); each shard owns its own LSH index and
contributes minibatch/S samples per global batch, weighted so the batch
mean equals the average of per-shard unbiased estimates (see
``repro/data/lsh_pipeline.py``).  On an elastic restart with a different
S, ``Trainer.restore`` rebuilds all per-shard indexes deterministically
from the restored params (``repro/train/elastic.py``).

Optimizer (``--optimizer {sgd,momentum,adagrad,adam}``): LGD only
replaces the gradient ESTIMATOR — the 1/(p·N) weights are applied
inside the jitted loss, so any update rule's moments accumulate the
unbiased estimate unchanged (gated end-to-end by
``benchmarks/run.py tab_optimizers``).

Multi-probe (``--multiprobe K``): walk K extra Hamming-ball probe
codes per table before giving up on it — empty buckets resolve to
probability-corrected near-bucket samples instead of uniform
fallbacks (watch the ``fallback`` column drop on skewed corpora).

LSH family (``--family {srp,mips}``): ``srp`` row-normalises the
pooled feature embeddings so cosine proxies the inner product (the
paper's BERT recipe); ``mips`` hashes them UN-normalised through the
asymmetric Simple-LSH augmentation (``repro/core/families/mips.py``)
— collision probability monotone in the raw inner product, so feature
norms carry sampling signal.  Same fused kernels either way.

Head (``--head {full,lsh}``): ``full`` pays the O(V·d)-per-token
softmax normaliser; ``lsh`` trains through the LSH-SAMPLED head
(``repro/models/sampled_softmax.py``): a MIPS index over the lm_head
rows is probed with each token's hidden state, the normaliser is
estimated from ``n_samples`` Algorithm-1 negatives with exact
inclusion probabilities (E[Zhat] = Z), and the index delta-refreshes
every ``refresh_every`` OPTIMIZER steps as the head trains — the
index-over-params twin of the data pipeline.  The eval line always
uses the exact full-vocab loss, so you can watch sampled training
track it.  ``--head lsh`` composes with ``--sampler uniform`` (the
LGD data sampler owns the batch stream in lgd mode).

Run:  PYTHONPATH=src python examples/train_lm.py [--preset demo]
          [--steps 200] [--sampler lgd] [--shards 2] [--ckpt /tmp/lm_ckpt]
          [--optimizer adam] [--multiprobe 2] [--family mips]
          [--head lsh]
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.data import (
    LSHPipelineConfig, ShardedLSHPipeline, lm_head_query_fn,
    make_token_corpus, mean_pool_feature_fn, uniform_batches,
)
from repro.models import (
    LMHeadIndex, ModelConfig, SampledSoftmaxConfig, init_params, loss,
    make_sampled_loss,
)
from repro.optim import make_optimizer, schedules
from repro.train import Trainer, TrainerConfig
from repro.launch.cache import enable_compile_cache

PRESETS = {
    "demo": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                 d_ff=512, vocab=1024, seq=64, corpus=4096, batch=16),
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=3072, vocab=32768, seq=512, corpus=100_000,
                 batch=32),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="demo", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--sampler", default="lgd", choices=["uniform", "lgd"],
                    help="uniform batches vs LSH-sampled LGD batches")
    ap.add_argument("--uniform", action="store_true",
                    help="deprecated alias for --sampler uniform")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard-by-example LSH index count (one per DP "
                         "group at scale); must divide the batch size")
    ap.add_argument("--refresh-mode", default="full",
                    choices=["full", "delta"],
                    help="full: re-hash the whole corpus each refresh; "
                         "delta: only visited + drift-sampled rows")
    ap.add_argument("--optimizer", default="adam",
                    choices=["sgd", "momentum", "adagrad", "adam"],
                    help="update rule; the LGD sampler composes with any "
                         "of them (importance weights enter the loss, so "
                         "moments accumulate the unbiased estimate)")
    ap.add_argument("--multiprobe", type=int, default=0,
                    help="extra Hamming-ball probe codes per table (0 = "
                         "single-probe): empty buckets resolve to "
                         "probability-corrected near-bucket samples "
                         "instead of uniform fallbacks")
    ap.add_argument("--family", default="srp", choices=["srp", "mips", "mips_banded"],
                    help="LSH family: srp = row-normalised features + "
                         "cosine SimHash; mips = un-normalised features "
                         "through the asymmetric Simple-LSH augmentation; "
                         "mips_banded = norm-ranged Simple-LSH (exact "
                         "weights at heavy-tailed feature norms)")
    ap.add_argument("--head", default="full", choices=["full", "lsh"],
                    help="full: exact O(V) softmax normaliser; lsh: "
                         "LSH-sampled normaliser over a MIPS index of "
                         "the lm_head rows, delta-refreshed by step")
    ap.add_argument("--head-refresh-every", type=int, default=25,
                    help="optimizer steps between head-index refreshes "
                         "(--head lsh)")
    ap.add_argument("--head-samples", type=int, default=64,
                    help="LSH-sampled negatives per token (--head lsh)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    if args.uniform:
        args.sampler = "uniform"
    if args.head == "lsh" and args.sampler == "lgd":
        ap.error("--head lsh composes with --sampler uniform (the LGD "
                 "data sampler owns the batch stream in lgd mode)")
    p = PRESETS[args.preset]

    cfg = ModelConfig(
        name=f"lm-{args.preset}", n_layers=p["n_layers"],
        d_model=p["d_model"], n_heads=p["n_heads"],
        n_kv_heads=p["n_kv_heads"], d_ff=p["d_ff"], vocab=p["vocab"],
        chunk=64, loss_chunk=128, dtype="float32", rope_theta=10000.0,
        lgd_enabled=args.sampler == "lgd")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params | sampler: {args.sampler}"
          f" | head: {args.head} | optimizer: {args.optimizer}"
          + (f" | shards: {args.shards} | multiprobe: {args.multiprobe}"
             f" | family: {args.family}"
             if cfg.lgd_enabled else ""))

    corpus = make_token_corpus(1, p["corpus"], p["seq"], cfg.vocab,
                               hard_frac=0.1)

    sampler = batches = None
    if cfg.lgd_enabled:
        sampler = ShardedLSHPipeline(
            jax.random.PRNGKey(2), corpus.tokens,
            mean_pool_feature_fn(cfg), lm_head_query_fn(),
            LSHPipelineConfig(k=cfg.lgd_k, l=cfg.lgd_l,
                              minibatch=p["batch"],
                              refresh_every=cfg.lgd_refresh_every,
                              refresh_async=True,
                              refresh_mode=args.refresh_mode,
                              multiprobe=args.multiprobe,
                              family=args.family),
            n_shards=args.shards, params=params)
    else:
        batches = uniform_batches(corpus, p["batch"], seed=3)

    head = loss_fn = step_hook = None
    if args.head == "lsh":
        # keep k in the populated-bucket regime at this preset's V
        # (occupancy ~ V / 2^k stays >> 1) so the sampled normaliser
        # sits inside the family's calibrated-unbiasedness boundary.
        scfg = SampledSoftmaxConfig(
            k=min(7, max(3, cfg.vocab.bit_length() - 6)), l=8,
            n_samples=args.head_samples, multiprobe=2,
            refresh_every=args.head_refresh_every, refresh_mode="delta")
        head = LMHeadIndex(params, cfg, scfg)
        batches = head.wrap_batches(batches)
        loss_fn = make_sampled_loss(cfg, scfg)
        step_hook = head.step_hook
        print(f"head index: {head.index.n_points} rows x "
              f"{head.index.n_tables} tables | m={scfg.n_samples} "
              f"negatives/token | refresh every {scfg.refresh_every} steps")

    peak = 3e-3 if args.optimizer == "adam" else 3e-2
    tr = Trainer(
        cfg, params,
        make_optimizer(args.optimizer,
                       schedules.warmup_cosine(peak, 20, args.steps)),
        batches,
        TrainerConfig(ckpt_dir=args.ckpt, ckpt_every=100, log_every=20,
                      donate=not cfg.lgd_enabled and args.head != "lsh",
                      step_hook=step_hook),
        sampler=sampler, loss_fn=loss_fn)

    eval_batch = {"tokens": jnp.asarray(corpus.tokens[:128, :-1]),
                  "targets": jnp.asarray(corpus.tokens[:128, 1:])}
    eval_fn = jax.jit(lambda prm: loss(prm, cfg, eval_batch))
    for chunk in range(0, args.steps, 50):
        n = min(50, args.steps - chunk)
        d0, w0 = tr.data_seconds, time.perf_counter()
        tr.run(n)
        wall = time.perf_counter() - w0
        # steps/sec + the fraction of wall time blocked on batch draws:
        # the device-resident data path shows up as sampler -> ~0.
        sampler_frac = (tr.data_seconds - d0) / max(wall, 1e-12)
        last = tr.metrics_history[-1] if tr.metrics_history else {}
        fb = (f"  fallback {sampler.sampler_stats()['fallback_rate']:5.1%}"
              if sampler is not None else "")
        print(f"step {tr.step:5d}  train {last.get('loss', float('nan')):.4f}"
              f"  eval {float(eval_fn(tr.params)):.4f}"
              f"  steps/s {n / max(wall, 1e-12):6.2f}"
              f"  sampler {sampler_frac:5.1%}{fb}"
              f"  stragglers {tr.straggler_steps}")
    tr.finalize()


if __name__ == "__main__":
    main()

"""Production training launcher: --arch <id> on a sharded mesh.

On a TPU fleet this binary runs once per host (jax.distributed picks up
the pod topology); on a CPU host it runs the same code path on the host
mesh with the arch's SMOKE config unless --full is given.

  PYTHONPATH=src python -m repro.launch.train --arch granite_3_8b \
      --steps 50 [--full] [--lgd] [--ckpt /tmp/ck] [--batch 8] [--seq 64]

``train`` is the path itself — mesh, sharded params, the LSH pipeline
and the ``Trainer`` — and is what ``chip_smoke.py`` drives as well.
"""

from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.data import (
    LSHPipelineConfig, ShardedLSHPipeline, lm_head_query_fn,
    make_token_corpus, mean_pool_feature_fn, uniform_batches,
)
from repro.dist.sharding import (
    data_axis_size, tree_param_shardings, use_mesh,
)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import ModelConfig, init_params
from repro.optim import Adam, schedules
from repro.train import Trainer, TrainerConfig

# tokens per jitted embedding call of an index (re)build: the pass holds
# about 0.4 GB of activations per 4096-token row at Granite-3-8B width.
EMBED_CHUNK_TOKENS = 8192


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          corpus: int, lr: float = 1e-3, lgd: bool = True,
          refresh_every: int = 200, multiprobe: int = 0,
          ckpt: str = None, mesh=None, log_every: int = 10) -> Trainer:
    """Build the sharded model, data path and ``Trainer``; run ``steps``.

    ``mesh`` defaults to ``make_host_mesh()`` (every local device on the
    data axis).  With ``lgd`` the batches come from a
    ``ShardedLSHPipeline`` — one LSH index per data-parallel shard,
    composed into an unbiased global estimator by the DP all-reduce —
    and the trainer keeps buffer donation off, since the pipeline's
    embedding pass reads the live params; ``multiprobe`` is the
    pipeline's count of extra Hamming-ball probe codes per table
    (``LSHPipelineConfig.multiprobe``).  Returns the trainer, so the
    caller can keep stepping, inspect it and ``finalize()`` it.
    """
    mesh = make_host_mesh() if mesh is None else mesh
    print(f"arch={cfg.name}  "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    with use_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        shardings = tree_param_shardings(params, mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, shardings)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"params: {n/1e6:.1f}M, sharded over {mesh.size} devices")

        if cfg.frontend == "embed_stub":
            raise SystemExit(
                f"{cfg.name} takes precomputed embeddings; use "
                "examples/serve.py or the dryrun for this arch")
        data = make_token_corpus(0, corpus, seq, cfg.vocab)

        sampler = batches = None
        if lgd:
            # shard-by-example: one LSH index per data-parallel group
            # (each queries only its corpus shard), composed into an
            # unbiased global estimator by the DP all-reduce.
            dp = data_axis_size(mesh)
            n_shards = dp if batch % dp == 0 else 1
            if n_shards != dp:
                print(f"WARNING: the DP degree {dp} does not divide "
                      f"batch={batch}; falling back to ONE global "
                      f"LSH index on host-placed batches (per-shard "
                      f"indexing disabled — every host re-embeds the "
                      f"full corpus on refresh)")
            sampler = ShardedLSHPipeline(
                jax.random.PRNGKey(2), data.tokens,
                mean_pool_feature_fn(cfg), lm_head_query_fn(),
                LSHPipelineConfig(minibatch=batch,
                                  refresh_every=refresh_every,
                                  multiprobe=multiprobe,
                                  refresh_async=True),
                n_shards=n_shards, params=params,
                feature_batch=max(1, min(512, EMBED_CHUNK_TOKENS // seq)),
                # device placement needs dim 0 divisible by the DP
                # degree; in the fallback it is not, so leave batches
                # host-side and let jit shard on entry.
                mesh=mesh if n_shards == dp else None)
        else:
            batches = uniform_batches(data, batch, seed=1)

        tr = Trainer(
            cfg, params,
            Adam(lr=schedules.warmup_cosine(lr, 10, steps)),
            batches,
            TrainerConfig(ckpt_dir=ckpt, ckpt_every=50, log_every=log_every,
                          donate=not lgd),
            sampler=sampler)
        tr.run(steps)
    return tr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--corpus", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the FULL production config (TPU fleets)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use make_production_mesh() instead of host mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--lgd", action="store_true",
                    help="enable the LSH-sampled data pipeline")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (configs.get(args.arch) if args.full
           else configs.get_smoke(args.arch))
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh())
    tr = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
               corpus=args.corpus, lr=args.lr, lgd=args.lgd,
               ckpt=args.ckpt, mesh=mesh)
    tr.finalize()
    for m in tr.metrics_history[-5:]:
        print(m)
    # where the host time went: every span's calls and seconds
    for name, (sec, calls) in sorted(tr.span_totals().items()):
        print(f"span {name}: {calls} calls, {sec:.3f} s")


if __name__ == "__main__":
    main()

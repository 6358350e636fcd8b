"""The system under test, assembled as ``repro.launch.train.train`` does.

``train()`` fixes its own corpus and keys, so the benchmark builds the same
pieces itself from the run's seed: parameters placed by
``tree_param_shardings``, a ``ShardedLSHPipeline`` with the launcher's
pipeline settings and embedding chunk, or ``uniform_batches`` for the
uniform twin, and a ``Trainer`` with donation off under LGD.  The
benchmark's tests pin the result to ``train()``'s first step.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.data import (
    LSHPipelineConfig, ShardedLSHPipeline, lm_head_query_fn,
    mean_pool_feature_fn, uniform_batches,
)
from repro.data.synthetic import TokenCorpus
from repro.dist.sharding import data_axis_size, use_mesh
from repro.launch.train import EMBED_CHUNK_TOKENS
from repro.optim import Adam, schedules
from repro.train import Trainer, TrainerConfig

LOG_EVERY = 10          # train()'s default logging cadence


def named_embed(cfg):
    """The pipeline's mean-pool embedding pass under a name of its own.

    The launcher's pass compiles as the anonymous ``jit_fn``; wrapped here
    it compiles as ``jit_refresh_embed`` (the same computation), and each
    call opens the host span ``bench/refresh_embed``.
    """
    inner = mean_pool_feature_fn(cfg)

    @jax.jit
    def refresh_embed(params, tokens):
        return inner(params, tokens)

    def fn(params, tokens):
        with TraceAnnotation("bench/refresh_embed"):
            return refresh_embed(params, tokens)
    return fn


def pipeline_config(traffic) -> LSHPipelineConfig:
    return LSHPipelineConfig(
        k=traffic["k"], l=traffic["l"], minibatch=traffic["batch"],
        refresh_every=traffic["refresh_every"],
        refresh_lead=traffic["refresh_lead"],
        multiprobe=traffic["multiprobe"], refresh_async=True)


def build_trainer(cfg, traffic, tokens, hard, params, mesh, *,
                  pipeline_key, uniform_seed, step_hook=None,
                  feature_fn=None):
    """A ``Trainer`` over ``tokens`` ((N, seq + 1) int32) from ``params``."""
    batch, seq = traffic["batch"], traffic["seq"]
    lgd = traffic["sampler"] == "lgd"
    with use_mesh(mesh):
        sampler = batches = None
        if lgd:
            dp = data_axis_size(mesh)
            if batch % dp:
                raise ValueError(f"batch {batch} does not divide over the "
                                 f"data-parallel degree {dp}")
            sampler = ShardedLSHPipeline(
                pipeline_key, tokens,
                feature_fn or named_embed(cfg), lm_head_query_fn(),
                pipeline_config(traffic), n_shards=dp, params=params,
                feature_batch=max(1, min(512, EMBED_CHUNK_TOKENS // seq)),
                mesh=mesh)
        else:
            corpus = TokenCorpus(np.asarray(tokens), np.asarray(hard))
            batches = uniform_batches(corpus, batch, seed=uniform_seed)
        return Trainer(
            cfg, params,
            Adam(lr=schedules.warmup_cosine(
                traffic["lr"], traffic["warmup_steps"],
                traffic["schedule_steps"])),
            batches,
            TrainerConfig(ckpt_dir=None, ckpt_every=50, log_every=LOG_EVERY,
                          donate=not lgd, step_hook=step_hook),
            sampler=sampler)

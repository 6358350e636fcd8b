"""Assigned input shapes and their ShapeDtypeStruct stand-ins.

Five shapes per LM architecture:
  train_4k     seq 4,096   global_batch 256   -> train_step
  prefill_32k  seq 32,768  global_batch 32    -> prefill (inference)
  decode_32k   seq 32,768  global_batch 128   -> serve_step (1 new token,
                                                 KV cache of seq_len)
  long_500k    seq 524,288 global_batch 1     -> serve_step; needs
                                                 sub-quadratic attention,
                                                 run only for SSM/hybrid
                                                 archs (cfg.supports_long_context)
  vocab_large  seq 4,096   global_batch 64    -> serve_step with the arch's
                                                 vocab OVERRIDDEN to 131,072
                                                 (production-LM vocab): the
                                                 dryrun/roofline-only cell
                                                 where the O(V·d) head
                                                 dominates the decode byte
                                                 budget and the LSH-sampled
                                                 softmax ratio is projected
                                                 (benchmarks/run.py
                                                 tab_softmax); never run as a
                                                 tier-1 compute cell.

A ``ShapeSpec.vocab`` override applies only on the abstract-eval paths
(``launch.dryrun.run_cell`` and ``launch.roofline``) — smoke/tier-1
configs keep their small vocabs so test runtime is unaffected.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import ModelConfig
from repro.models.lm import ATTN_KINDS
from repro.models import ssm as ssm_mod


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str     # "train" | "prefill" | "decode"
    # when set, the cell runs with cfg.vocab overridden (dryrun/roofline
    # abstract-eval only — see apply_vocab)
    vocab: Optional[int] = None


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
    "vocab_large": ShapeSpec("vocab_large", 4_096, 64, "decode",
                             vocab=131_072),
}


def apply_vocab(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """The config the cell actually runs: vocab overridden when the
    shape pins one (vocab_large), unchanged otherwise."""
    if shape.vocab is None or shape.vocab == cfg.vocab:
        return cfg
    return dataclasses.replace(cfg, vocab=shape.vocab)


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    """None if the (arch, shape) cell runs; else the skip reason."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention arch: 500k-token context is "
                "quadratic-prefill/O(seq) KV-cache territory reserved for "
                "sub-quadratic mixers per the assignment (see DESIGN.md)")
    return None


def _f(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _i(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, object]:
    """ShapeDtypeStruct stand-ins for the model-input batch dict."""
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    specs: Dict[str, object] = {}
    if shape.kind == "decode":
        if cfg.frontend == "embed_stub":
            specs["embeds"] = _f((b, 1, cfg.d_model), dt)
        else:
            specs["tokens"] = _i((b, 1))
        specs["positions"] = _i((b, 1))
    else:
        if cfg.frontend == "embed_stub":
            specs["embeds"] = _f((b, s, cfg.d_model), dt)
        else:
            specs["tokens"] = _i((b, s))
        if shape.kind == "train":
            specs["targets"] = _i((b, s))
    if "cross_attn" in cfg.block_pattern:
        specs["image_embeds"] = _f((b, max(cfg.n_patches, 1), cfg.d_model), dt)
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeSpec) -> list:
    """ShapeDtypeStructs matching models.lm.init_cache output."""
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    r = cfg.repeats
    out = []
    for kind in cfg.block_pattern:
        if kind in ATTN_KINDS:
            out.append({"attn": {
                "k": _f((r, b, s, cfg.n_kv_heads, cfg.d_head), dt),
                "v": _f((r, b, s, cfg.n_kv_heads, cfg.d_head), dt),
                "len": _i((r, b)),
            }})
        elif kind == "mamba2":
            d_inner = cfg.ssm_expand * cfg.d_model
            nh = d_inner // cfg.ssm_head_dim
            out.append({"state": {
                "ssm": _f((r, b, nh, cfg.ssm_state, cfg.ssm_head_dim)),
                "conv": _f((r, b, cfg.ssm_conv - 1,
                            d_inner + 2 * cfg.ssm_state), dt),
            }})
        elif kind == "mlstm":
            dh = cfg.d_model // cfg.n_heads
            out.append({"state": _f((r, b, cfg.n_heads, dh, dh + 1))})
        elif kind == "slstm":
            out.append({"state": tuple(
                _f((r, b, cfg.d_model)) for _ in range(3))})
        else:
            raise ValueError(kind)
    return out

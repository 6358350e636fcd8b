"""Roofline analysis from dry-run artifacts (TPU v5e targets).

Per (arch x shape x mesh) cell, derive the three roofline terms:

  compute    = HLO_FLOPs_per_device / peak_FLOPs          [s]
  memory     = HLO_bytes_per_device / HBM_bw              [s]
  collective = wire_bytes_per_device / ICI_bw             [s]

Hardware constants (v5e): 197 TFLOP/s bf16; 819 GB/s HBM; ~50 GB/s/link
ICI (we budget a single link — conservative).

Wire-byte model per collective op (result bytes R, ring algorithms):
  all-gather           R * (n-1)/n   ~ R
  reduce-scatter       R * (n-1)     (input is n*R)     ~ n*R — but the
                                     parsed result IS the shard, so we
                                     charge R (the per-hop traffic) * 2
  all-reduce           2R * (n-1)/n  ~ 2R
  all-to-all           R * (n-1)/n   ~ R
  collective-permute   R
Group sizes are not recovered from the HLO here, so the asymptotic
(n-1)/n ~ 1 approximation is used; this slightly over-charges small
groups (documented in EXPERIMENTS.md).

MODEL_FLOPS uses the classic 6*N*D (train) / 2*N*D (inference) with N =
ACTIVE parameters (MoE: top_k experts only); the ratio
MODEL_FLOPS / HLO_FLOPs exposes remat recompute, causal-mask waste and
sharding replication.
"""

from __future__ import annotations

import argparse
import json
from typing import Dict

from repro import configs
from repro.configs.shapes import SHAPES, apply_vocab
from repro.models import ModelConfig

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s (one link)

_WIRE_FACTOR = {
    "all-gather": 1.0,
    "reduce-scatter": 2.0,
    "all-reduce": 2.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _block_kinds(cfg: ModelConfig) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for b in cfg.block_pattern:
        out[b] = out.get(b, 0) + 1
    return out


def active_params(cfg: ModelConfig) -> float:
    """Active parameters per token (MoE: routed experts only)."""
    d, dh = cfg.d_model, cfg.d_head
    counts = _block_kinds(cfg)
    per_pattern = 0.0
    for kind, cnt in counts.items():
        blk = 0.0
        if kind in ("attn", "shared_attn", "cross_attn"):
            blk += d * dh * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)  # qkvo
            if kind == "cross_attn":
                blk *= 2
        elif kind == "mamba2":
            d_inner = cfg.ssm_expand * d
            nh = d_inner // cfg.ssm_head_dim
            blk += d * (2 * d_inner + 2 * cfg.ssm_state + nh)
            blk += d_inner * d
        elif kind == "mlstm":
            blk += d * 3 * d + d * 2 * cfg.n_heads + d * d
        elif kind == "slstm":
            blk += d * 4 * d + d * d
        if kind in ("attn", "shared_attn", "cross_attn") or (
                kind == "mamba2" and cfg.ssm_ffn):
            if cfg.is_moe:
                n_mats = 3
                blk += d * cfg.moe_experts  # router (all tokens)
                blk += cfg.moe_top_k * n_mats * d * cfg.moe_d_ff
            elif cfg.d_ff:
                n_mats = 3 if cfg.act == "swiglu" else 2
                blk += n_mats * d * cfg.d_ff
        per_pattern += cnt * blk
    total = per_pattern * cfg.repeats
    total += 2 * cfg.vocab * d          # embed + head
    return total


def model_flops(cfg: ModelConfig, shape, n_devices: int) -> float:
    """Analytic useful FLOPs per device for the cell."""
    n = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
        # attention reads over the KV cache: 2 * 2 * Hkv*Dh * S per layer
        n_attn_layers = sum(
            1 for b in cfg.block_pattern
            if b in ("attn", "shared_attn", "cross_attn")) * cfg.repeats
        total += (4.0 * cfg.n_heads * cfg.d_head * shape.seq_len
                  * n_attn_layers * shape.global_batch)
    return total / n_devices


def roofline_terms(record: dict) -> dict:
    cfg = apply_vocab(configs.get(record["arch"]), SHAPES[record["shape"]])
    shape = SHAPES[record["shape"]]
    n_dev = record["n_devices"]
    compute_t = record["flops_per_device"] / PEAK_FLOPS
    memory_t = record["bytes_per_device"] / HBM_BW
    wire = sum(_WIRE_FACTOR.get(k, 1.0) * v
               for k, v in record["collectives"].items())
    coll_t = wire / ICI_BW
    mf = model_flops(cfg, shape, n_dev)
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dominant = max(terms, key=terms.get)
    useful_t = mf / PEAK_FLOPS
    bound = max(compute_t, memory_t, coll_t)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "model_flops_per_device": mf,
        "useful_flops_ratio": mf / max(record["flops_per_device"], 1),
        # fraction of roofline-achievable throughput this cell realises,
        # assuming perfect overlap: useful work time / max(term)
        "roofline_fraction": useful_t / max(bound, 1e-12),
        "step_time_lower_bound_s": bound,
    }


_ADVICE = {
    ("compute",): "cut replicated/recomputed FLOPs: pad-shard heads, "
                  "drop causal-mask waste (Pallas kernel), looser remat",
    ("memory",): "raise arithmetic intensity: fuse, bigger blocks, bf16 "
                 "intermediates, avoid re-streaming weights",
    ("collective",): "reduce resharding: fold FSDP gathers into the scan, "
                     "overlap collectives with compute, shrink all-reduces",
}


def build_table(records: list) -> str:
    lines = [
        "| arch | shape | mesh | compute_s | memory_s | collective_s | "
        "dominant | MODEL/HLO flops | roofline frac | fix |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        if "skipped" in r:
            lines.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | — | SKIPPED | "
                f"— | — | {r['skipped'][:60]}… |")
            continue
        if "error" in r:
            lines.append(
                f"| {r['arch']} | {r['shape']} | — | ERROR | — | — | — | — "
                f"| — | {r['error'][:60]} |")
            continue
        t = roofline_terms(r)
        advice = _ADVICE[(t["dominant"],)]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {t['compute_s']:.3e} | {t['memory_s']:.3e} "
            f"| {t['collective_s']:.3e} | **{t['dominant']}** "
            f"| {t['useful_flops_ratio']:.2f} "
            f"| {t['roofline_fraction']:.3f} | {advice[:52]}… |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    records = json.load(open(args.inp))
    table = build_table(records)
    enriched = []
    for r in records:
        if "skipped" not in r and "error" not in r:
            r = {**r, "roofline": roofline_terms(r)}
        enriched.append(r)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(enriched, f, indent=2, default=str)
    print(table)


if __name__ == "__main__":
    main()

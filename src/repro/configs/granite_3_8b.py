"""granite-3-8b [dense] — GQA [hf:ibm-granite/granite-3.0-8b-base; hf].

40L d_model=4096 32H (GQA kv=8) d_head=128 d_ff=12800 vocab=49155,
SwiGLU, RMSNorm, RoPE, 4096-token context.

Departures from the published model (all configs here share one LM):
  * the scaling multipliers are not modelled — embedding_multiplier 12,
    residual_multiplier 0.22, attention_multiplier 1/128 (scores here
    are scaled by d_head^-0.5 = 1/11.3) and logits_scaling 16;
  * the output head is a separate (untied) matrix;
  * rope_theta is the ModelConfig default, not the published 10000.

``ONE_CHIP`` is the cut that trains on one TPU v5e chip (16 GB HBM)
through the normal LGD path; ``ONE_CHIP_TRAIN`` holds its job shapes.
"""

from repro.models import ModelConfig

SOURCE = "https://huggingface.co/ibm-granite/granite-3.0-8b-base (config.json)"

FULL = ModelConfig(
    name="granite-3-8b",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=12800,
    vocab=49155,
    act="swiglu",
)

# The deployment ONE_CHIP stands for: the 40 layers as stages of a
# pipeline over 40 chips, so one chip holds one whole layer (a dense
# model's layer pattern has period one) plus the embedding and the
# untied head, whole — the vocabulary is not sliced.  Every width is as
# published.  Measured with ``compiled.memory_analysis()`` of the real
# Trainer step for v5e (Adam with f32 m and v, donation off, as the LGD
# sampler requires): 601.9M parameters, batch 2 x 4096 -> 6.020 GB
# arguments + 6.020 GB outputs + 0.050 GB temporaries = 12.09 GB,
# leaving over 3.5 GB for the pipeline's store, features, index and its
# 2-row embedding pass (0.84 GB of temporaries).  Two layers would need
# 16.1 GB at batch 1.
REDUCED = {"n_layers": (40, 1)}          # key: (published, held here)
DEPLOYMENT = ("40-stage pipeline over 40 chips, one layer per chip; "
              "embedding and head whole")

ONE_CHIP = FULL.with_(name="granite-3-8b-1chip", n_layers=1)

# job settings of the one-chip cell: Granite 3.0's full 4096-token
# context, two sequences per step (batch 4 reaches 15.5 GB: no room for
# the pipeline), and a corpus shard of 1024 rows re-embedded every
# ``refresh_every`` steps.  Queries probe the whole radius-2 Hamming
# ball (1 + 7 + 21 codes per table at K=7): mean-pooled 4096-token
# features of an untrained model point almost one way (median pairwise
# cosine 0.97) and the lm_head query is near-orthogonal to them, so the
# exact bucket is empty in every table and single-probe draws all fall
# back to uniform; the probe walk keeps the 1/(pN) weights exact.
ONE_CHIP_TRAIN = dict(seq=4096, batch=2, corpus=1024, multiprobe=28)

SMOKE = FULL.with_(
    name="granite-3-smoke",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=128,
    vocab=128,
    chunk=16,
    loss_chunk=16,
    dtype="float32",
)

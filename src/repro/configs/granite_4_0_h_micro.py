"""granite-4.0-h-micro [hybrid] — Mamba-2 + NoPE GQA [hf:ibm-granite/granite-4.0-h-micro].

40L d_model=2048 in periods of 10 layers: five Mamba-2 layers, one
attention layer, four Mamba-2 layers (``layer_types[0:10]``).  Mamba-2:
64 heads of 64 (expand 2), d_state 128, one B/C group, chunk 256, causal
conv width 4 with bias, gated RMSNorm before out_proj.  Attention: GQA
32/8, d_head 64, no positional encoding (NoPE).  A SwiGLU MLP of 8192 in
every layer.  embedding_multiplier 12, residual_multiplier 0.22,
attention_multiplier 1/64.  Vocabulary 100,352.

Departures from the published model:
  * the output head is a separate (untied) matrix, and logits are not
    divided by logits_scaling 8: the program has neither a tied head nor
    a logit scale.

``ONE_CHIP`` is the cut that trains on one TPU v5e chip (16 GB HBM)
through the normal path.
"""

from repro.models import ModelConfig

PERIOD = ("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4

FULL = ModelConfig(
    name="granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    block_pattern=PERIOD,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_ffn=True,
    chunk=256,
    rope=False,
    rope_theta=10000.0,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1 / 64,
)

# The deployment ONE_CHIP stands for: the 40 layers as 4 pipeline stages
# of one period each, with the embedding and the head split by vocabulary
# over 8 chips, so this chip holds one whole period (every kind of layer
# in its published ratio) and an eighth of the vocabulary (12,544 of
# 100,352 rows).  Every width is as published.  797.9M parameters: nine
# Mamba-2 layers of 76.18M (mixer 25.85M, MLP 50.33M), one attention layer
# of 60.82M, an embedding and an untied head of 25.69M each.  LGD keeps
# its training state twice (no donation), 15.96 GB at 10 B a parameter:
# more than the chip, so the one-chip cell trains with uniform draws,
# whose step donates.  ``compiled.memory_analysis()`` of the Trainer step
# compiled for a v5e (Adam with f32 m and v, donation on), batch 1 x 8192:
# 7.979 GB arguments (the outputs alias them) + 3.275 GB temporaries =
# 11.25 GB.  The chip's ``peak_bytes_in_use`` after a run reads 8.32 GB.
ONE_CHIP = FULL.with_(name="granite-4.0-h-micro-1chip", n_layers=10,
                      vocab=12544)

SMOKE = FULL.with_(
    name="granite-4.0-h-smoke",
    n_layers=20,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=128,
    vocab=128,
    ssm_state=16,
    ssm_head_dim=16,
    chunk=16,
    loss_chunk=16,
    dtype="float32",
)

"""Unified model configuration covering all assigned architectures."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0                   # 0 -> d_model // n_heads

    # activation / FFN
    act: str = "swiglu"               # swiglu | gelu | squared_relu

    # MoE (0 experts = dense)
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_capacity_factor: float = 1.25

    # layer pattern, cycled to n_layers.  Block kinds:
    #   attn          self-attention + FFN
    #   cross_attn    self-attn + cross-attn(image) + FFN  (vision layers)
    #   mamba2        Mamba-2 SSD block
    #   mlstm         xLSTM matrix-LSTM block
    #   slstm         xLSTM scalar-LSTM block
    #   shared_attn   attention block with weights shared across repeats
    block_pattern: Tuple[str, ...] = ("attn",)

    # sequence-mixer extras
    ssm_state: int = 0                # Mamba2 state size N
    ssm_head_dim: int = 64            # Mamba2/mLSTM head dim P
    ssm_expand: int = 2               # d_inner = expand * d_model
    ssm_conv: int = 4                 # Mamba2 causal depthwise conv width
    ssm_ffn: bool = False             # mamba2 layers carry the FFN too
    chunk: int = 256                  # chunked-scan length for SSM/linear attn

    # modality frontend: "none" = token ids; "embed_stub" = precomputed
    # frame/patch embeddings are the input (audio/vlm backbones).
    frontend: str = "none"
    n_patches: int = 0                # vision: image patch count (stub)

    rope_theta: float = 500000.0
    rope: bool = True                 # rotary positions (False: NoPE)
    norm_eps: float = 1e-5

    # Granite-style scalings: the embedding output, every mixer and FFN
    # branch before its residual add, and the attention score scale
    # (0 -> d_head^-0.5)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    dtype: str = "bfloat16"

    # execution knobs
    attn_impl: str = "chunked"        # chunked (XLA flash) | ref | pallas
    attn_block_q: int = 512           # q-block for the chunked scan
    # sequence parallelism: shard the residual stream's seq dim over the
    # model axis at layer boundaries (Megatron-SP) — divides saved remat
    # activations and norm/embedding work by the TP degree.
    seq_shard: bool = True
    remat: bool = True
    loss_chunk: int = 1024            # vocab-projection chunk (tokens)
    scan_layers: bool = True          # lax.scan over pattern repeats

    # LGD integration (data-pipeline-level adaptive sampling)
    lgd_enabled: bool = False
    lgd_k: int = 7
    lgd_l: int = 10
    lgd_refresh_every: int = 200

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        assert self.n_heads % self.n_kv_heads == 0, (
            self.n_heads, self.n_kv_heads)
        if self.n_layers % len(self.block_pattern) != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} not a multiple of "
                f"pattern length {len(self.block_pattern)}")

    @property
    def repeats(self) -> int:
        return self.n_layers // len(self.block_pattern)

    @property
    def is_moe(self) -> bool:
        return self.moe_experts > 0

    @property
    def is_attention_free(self) -> bool:
        return all(b in ("mamba2", "mlstm", "slstm")
                   for b in self.block_pattern)

    @property
    def has_ssm(self) -> bool:
        return any(b in ("mamba2", "mlstm", "slstm")
                   for b in self.block_pattern)

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic archs: SSM/hybrid/linear-attn run long_500k."""
        return self.has_ssm

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

"""Configuration dataclasses for the repro framework.

Every architecture is described by a ``ModelConfig``; every dry-run cell's
training shape by a ``ShapeConfig``. Configs are plain frozen dataclasses so
they hash, print, and serialize cleanly.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    d_expert: int = 0              # expert FFN hidden size (fine-grained may differ from d_ff)
    capacity_factor: float = 1.25  # for the capacity-based (shardable) path
    router_jitter: float = 0.0
    # impl: "capacity" (einsum dispatch, shards via GSPMD; used for dry-run/train)
    #       "dropless" (sort + ragged gmm; exact, used by the serving engine)
    impl: str = "capacity"
    norm_topk: bool = True         # renormalize the top-k router weights to sum 1
                                   # (DeepSeek's norm_topk_prob; Mixtral's softmax
                                   # over the top-k logits is the same as True)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) without a
    query LoRA: a token caches one normed latent of ``kv_lora_rank`` and one
    roped key of ``qk_rope_head_dim`` shared by every head; each head's key
    is its up-projected latent (``qk_nope_head_dim``) joined with that rope
    key, and its value the latent up-projected to ``v_head_dim``."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_dim(self) -> int:
        """Values one token caches per layer: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (``rope_scaling.type`` "yarn", as DeepSeek-V2 has it)."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec models (seamless). Frontend is a stub:
    the encoder consumes precomputed frame embeddings (B, frames, d_model)."""
    n_layers: int = 24
    cross_attn_memory: int = 1024  # encoder memory length seen by decode shapes


@dataclass(frozen=True)
class VisionConfig:
    """VLM frontend stub: precomputed patch embeddings (B, n_patches, d_patch)
    plus a real, sharded linear projector into the LM d_model."""
    n_patches: int = 576
    d_patch: int = 1024


@dataclass(frozen=True)
class LayerGroup:
    """A repeating block pattern. ``pattern`` is a string over:
      'A' full attention    'L' local (sliding-window) attention
      'G' global attention  'M' mamba2 (SSD)
    ``moe_mask`` marks which positions within the pattern use a MoE MLP
    (None = all dense, or a string of '0'/'1' with len == len(pattern)).
    Params for a group are stacked on a leading ``repeats`` dim and the body
    runs as a lax.scan over repeats.
    """
    pattern: str
    repeats: int
    moe_mask: Optional[str] = None

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.repeats


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-6
    sliding_window: int = 0        # >0: window size for 'L' layers
    attn_softcap: float = 0.0      # gemma2: 50.0
    logit_softcap: float = 0.0     # gemma2: 30.0
    tie_embeddings: bool = False
    scale_embedding: bool = False  # gemma: x *= sqrt(d_model) after embed
    dense_d_ff: int = 0            # deepseek: first layer dense-FFN width
    act: str = "silu"              # silu (SwiGLU) | gelu
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionConfig] = None
    layer_groups: Tuple[LayerGroup, ...] = ()
    mla: Optional[MLAConfig] = None     # set: the attention layers are latent
    rope_yarn: Optional[YarnConfig] = None   # YaRN on the latent layers' rope
    source: str = ""               # provenance tag from the assignment table

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        if not self.layer_groups:
            pat = "M" if self.family == "ssm" else "A"
            object.__setattr__(
                self, "layer_groups", (LayerGroup(pattern=pat, repeats=self.n_layers),)
            )
        got = sum(g.n_layers for g in self.layer_groups)
        assert got == self.n_layers, f"{self.name}: layer_groups cover {got} != n_layers {self.n_layers}"
        assert self.rope_yarn is None or self.mla is not None, \
            f"{self.name}: YaRN rope is computed by the latent attention layers only"

    # -- derived ------------------------------------------------------------
    @property
    def attn_free(self) -> bool:
        return all(c == "M" for g in self.layer_groups for c in g.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm.expand * self.d_model if self.ssm else 0

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm.head_dim if self.ssm else 0

    def param_count(self) -> int:
        """Analytic parameter count (matches init_params; used for 6ND)."""
        from repro.models.params import count_params_analytic
        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.params import count_params_analytic
        return count_params_analytic(self, active_only=True)

    def scaled(self, **overrides) -> "ModelConfig":
        return dataclasses.replace(self, **overrides)


@dataclass(frozen=True)
class ShapeConfig:
    """A training step's shape for the dry-run: sequence length and global
    batch."""
    name: str
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256)
SHAPES = {s.name: s for s in (TRAIN_4K,)}

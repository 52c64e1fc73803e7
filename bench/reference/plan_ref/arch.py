"""The architecture record the planner reads (a frozen copy of the
program's ``configs.ArchConfig`` and its sub-records), and
:func:`from_config`, which fills it from a configuration file: the
published ``config.json`` keys, plus the training-system settings that
the file lists under ``assumed``."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 0           # 0 => no q compression
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 64
    top_k: int = 2
    n_shared_experts: int = 0
    d_expert: int = 1408           # per-expert FFN hidden dim
    n_dense_layers: int = 0        # leading layers that use a dense FFN instead
    dense_residual: bool = False   # arctic: dense FFN in parallel with MoE
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class VLMConfig:
    d_vision: int = 1024           # vision-tower output width
    n_image_tokens: int = 576      # tokens contributed by the image
    projector_layers: int = 2
    vision_tower: bool = False     # True => real ViT params (paper repro);
                                   # False => stubbed frontend (assigned arch)
    vit_layers: int = 24
    vit_heads: int = 16
    vit_d_ff: int = 4096
    vit_patch: int = 14
    vit_image_size: int = 336


@dataclass(frozen=True)
class EncDecConfig:
    n_enc_layers: int = 24
    d_frontend: int = 1024         # stubbed speech-frontend embedding width
    enc_seq_ratio: float = 1.0     # encoder seq = ratio * shape.seq_len


@dataclass(frozen=True)
class HybridConfig:
    attn_every: int = 6            # shared attention block applied every k layers
    shared_attn_blocks: int = 2    # distinct shared blocks, alternating


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 => d_model // n_heads
    qk_norm: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    vlm: Optional[VLMConfig] = None
    encdec: Optional[EncDecConfig] = None
    hybrid: Optional[HybridConfig] = None
    # training-system defaults (overridable by TrainConfig)
    optimizer: str = "adamw"       # adamw | adafactor | adamw8bit
    fsdp: bool = False             # shard params over the data axis too (ZeRO-3)
    remat: str = "block"           # none | block | dots
    seq_parallel: bool = True      # shard the residual seq dim over `model`
    subquadratic: bool = False     # may run long_500k
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)



@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"


def from_config(name: str, cfg: dict) -> ArchConfig:
    """The ArchConfig of a decoder-only LM from its config.json keys."""
    a = cfg.get("assumed", {})
    mla = None
    if cfg.get("kv_lora_rank"):
        mla = MLAConfig(q_lora_rank=cfg.get("q_lora_rank") or 0,
                        kv_lora_rank=cfg["kv_lora_rank"],
                        qk_nope_head_dim=cfg["qk_nope_head_dim"],
                        qk_rope_head_dim=cfg["qk_rope_head_dim"],
                        v_head_dim=cfg["v_head_dim"])
    moe = None
    if cfg.get("n_routed_experts"):
        moe = MoEConfig(n_experts=cfg["n_routed_experts"],
                        top_k=cfg["num_experts_per_tok"],
                        n_shared_experts=cfg.get("n_shared_experts") or 0,
                        d_expert=cfg["moe_intermediate_size"],
                        n_dense_layers=cfg.get("first_k_dense_replace", 0),
                        capacity_factor=a["moe_capacity_factor"])
    return ArchConfig(
        name=name, family="moe" if moe else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or 0,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        mla=mla, moe=moe, optimizer=a["optimizer"], fsdp=a["fsdp"],
        remat=a["remat"], seq_parallel=a["seq_parallel"])

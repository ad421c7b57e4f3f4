"""Architecture config schema + registry for the 10 assigned architectures.

Block kinds used in ``pattern`` (the repeating layer unit):
  attn        self-attention (GQA) + FFN
  mla         multi-head latent attention + FFN (deepseek)
  cross       cross-attention to frontend embeddings + FFN (vlm)
  dec         decoder layer: self-attn + cross-attn + FFN (enc-dec)
  mamba       Mamba2 block (no FFN)
  mlstm/slstm xLSTM blocks (no FFN)
  shared_attn zamba2 shared transformer block (weights shared across uses)
  hybrid      Zamba2-7B hybrid layer: one of ``mem_blocks`` shared attention
              + FFN blocks (used in turn) over concat(x, embeddings), a
              per-use LoRA on its FFN and a per-use output projection,
              added to a Mamba2 layer's input
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.models.moe import MoEConfig


@dataclasses.dataclass(frozen=True)
class MLASpec:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense|vlm|audio|ssm|moe|hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    norm: str = "rmsnorm"
    act: str = "silu"
    gated_ffn: bool = True
    parallel_block: bool = False     # command-r: attn and FFN in parallel
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None
    tie_embeddings: bool = False
    pattern: Tuple[str, ...] = ("attn",)
    moe: Optional[MoEConfig] = None
    moe_dense_residual: bool = False # arctic: dense FFN in parallel with MoE
    moe_first_dense: int = 0         # deepseek: first k layers use dense FFN
    dense_ff: int = 0                # hidden of dense residual / first-dense FFN
    mla: Optional[MLASpec] = None
    ssm_state: int = 64
    mamba_ngroups: int = 1           # Mamba2 groups of B and C
    mem_blocks: int = 1              # hybrid: shared blocks, used in turn
    adapter_rank: int = 0            # hybrid: rank of the per-use FFN LoRA
    attn_scale: Optional[float] = None  # softmax scale (None: hd ** -0.5)
    encoder_layers: int = 0          # seamless enc-dec
    frontend: Optional[str] = None   # "vision"|"audio" — STUB embeddings
    frontend_tokens: int = 0
    subquadratic: bool = False       # eligible for long_500k
    param_dtype: str = "bfloat16"
    # ODiMO-on-TPU serve-time precision domains (DESIGN.md §2):
    kv_cache_dtype: str = "bfloat16"   # "int8": quantized KV/latent cache
    serve_weight_dtype: str = "bfloat16"  # "int8": quantized projections

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def pattern_repeats(self) -> int:
        assert self.n_layers % len(self.pattern) == 0 or self.name.startswith("zamba"), \
            (self.name, self.n_layers, len(self.pattern))
        return self.n_layers // len(self.pattern)


_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get(name: str) -> ArchConfig:
    if not _REGISTRY:
        load_all()
    return _REGISTRY[name]


def names():
    if not _REGISTRY:
        load_all()
    return sorted(_REGISTRY)


def load_all():
    """Import every config module (each self-registers)."""
    from repro.configs import (  # noqa: F401
        command_r_35b, nemotron_4_15b, yi_9b, h2o_danube_3_4b,
        llama_3_2_vision_11b, seamless_m4t_large_v2, xlstm_1_3b,
        arctic_480b, deepseek_v2_lite_16b, zamba2_1_2b, zamba2_7b,
    )


def reduce_for_smoke(cfg: ArchConfig) -> ArchConfig:
    """Shrink a config to CPU-smoke-test size, preserving the family shape."""
    pattern = cfg.pattern
    if "hybrid" in pattern:
        # every shared block used twice, each use after a Mamba2 layer
        pattern = ("mamba", "hybrid") * (2 * cfg.mem_blocks)
    period = len(pattern)
    n_layers = period * min(2, max(1, cfg.n_layers // period))
    if cfg.name.startswith("zamba"):
        n_layers = period + 2  # one full period + the remainder mambas
    if "hybrid" in pattern:
        n_layers = period
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, n_experts=8,
                                  top_k=min(cfg.moe.top_k, 2), d_ff=64)
    mla = MLASpec(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                  v_head_dim=16) if cfg.mla else None
    return dataclasses.replace(
        cfg, n_layers=n_layers, pattern=pattern, d_model=64,
        n_heads=4, n_kv_heads=min(4, max(1, cfg.n_kv_heads)), head_dim=16,
        d_ff=128 if cfg.d_ff else 0, vocab=128, moe=moe, mla=mla,
        dense_ff=96 if cfg.dense_ff else 0,
        encoder_layers=min(2, cfg.encoder_layers),
        frontend_tokens=8 if cfg.frontend_tokens else 0,
        sliding_window=16 if cfg.sliding_window else None,
        adapter_rank=8 if cfg.adapter_rank else 0,
        # the scale keeps its ratio to head_dim ** -0.5
        attn_scale=(cfg.attn_scale * (cfg.hd / 16) ** 0.5
                    if cfg.attn_scale else None))

"""Model configuration covering all assigned architecture families."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid
    n_layers: int
    d_model: int
    vocab: int
    modality: str = "text"         # text | audio | vlm (stub frontends)

    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0              # 0 → d_model // n_heads
    qk_norm: bool = False
    attn_bias: bool = False
    rope_theta: float = 10_000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # M-RoPE (qwen2-vl)

    # dense MLP
    d_ff: int = 0
    mlp_act: str = "swiglu"        # swiglu | gelu | relu2
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    moe_period: int = 1            # MoE replaces MLP every k-th layer
    moe_offset: int = 0
    capacity_factor: float = 1.25
    moe_dropless: bool = False     # capacity = group size (exact; serving/tests)
    moe_group_size: int = 1024     # GShard token-group size: dispatch cost is
                                   # O(N·S), capacity is per-group

    # Mamba2 / SSD
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 256

    # hybrid interleave (jamba): 1 attention layer per attn_period layers
    attn_period: int = 0
    attn_offset: int = 0

    # numerics / execution
    norm_eps: float = 1e-5
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing"  # nothing|dots — what the checkpoint saves
    attn_chunk_q: int = 0          # 0 → naive attention; else flash-style chunk
    tie_embeddings: bool = False
    scan_unroll: bool = False      # unroll all scans (dry-run cost probes:
                                   # HloCostAnalysis counts while bodies once)
    # §Perf levers (defaults = paper-faithful baseline behavior)
    prefill_last_only: bool = False   # L2: slice hidden before LM head
    attn_mask_mode: str = "where"     # L3a: where | additive
    softmax_dtype: str = "float32"    # L3b: float32 | bfloat16 score pipeline
    moe_impl: str = "dense"           # L4: dense (GShard one-hot) | sorted
    attn_impl: str = "reference"      # L8: reference | lean (minimal-pass
                                      # softmax, replicated bias, late divide)
    cache_mode: str = "scatter"       # L9: scatter (ragged rows, general) |
                                      # slice (uniform positions — GSPMD-local
                                      # dynamic_update_slice, no gather)
    paged_attn: str = "auto"          # paged-attention read: auto (fused
                                      # on TPU, else gather) | gather (XLA
                                      # page-table gather) | fused (Pallas
                                      # in-kernel page walk)
    kv_dtype: str = "fp16"            # paged KV page storage: fp16 (compute-
                                      # dtype pages, today's layout) | int8 |
                                      # int4 (packed nibbles) with in-page
                                      # per-(slot, head) dequant scales

    # ---- derived ------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // max(1, self.n_heads))

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim_

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim_

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        # mamba2 convolves x together with B and C streams
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def period(self) -> int:
        """Layer-pattern period (1 for homogeneous stacks)."""
        p = 1
        if self.family == "hybrid" and self.attn_period:
            p = self.attn_period
        if self.n_experts and self.moe_period > 1:
            p = math.lcm(p, self.moe_period)
        return p

    @property
    def n_periods(self) -> int:
        assert self.n_layers % self.period == 0, (self.n_layers, self.period)
        return self.n_layers // self.period

    def mixer_kind(self, pos: int) -> str:
        """Mixer of layer-position ``pos`` within a period: attn | mamba."""
        if self.family == "ssm":
            return "mamba"
        if self.family == "hybrid":
            return "attn" if pos % self.attn_period == self.attn_offset else "mamba"
        return "attn"

    def ffn_kind(self, pos: int) -> str:
        """FFN of layer-position ``pos``: mlp | moe | none."""
        if self.family == "ssm":
            return "none"
        if self.n_experts and pos % self.moe_period == self.moe_offset:
            return "moe"
        return "mlp"

    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    def pdtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

"""Registry of assigned architectures and their input-shape sets.

Every entry is from public literature — source tags inline. Shapes:
  train_4k     seq 4096,   global batch 256  (train_step)
  prefill_32k  seq 32768,  global batch 32   (prefill)
  decode_32k   seq 32768,  global batch 128  (single-token decode, KV cache)
  long_500k    seq 524288, global batch 1    (long-context decode; runs only
               for sub-quadratic mixers: ssm/hybrid — see DESIGN.md)
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


LM_SHAPES = (
    ShapeSpec("train_4k", 4_096, 256, "train"),
    ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    ShapeSpec("decode_32k", 32_768, 128, "decode"),
    ShapeSpec("long_500k", 524_288, 1, "decode"),
)


def _sub_quadratic(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid")


def shapes_for(cfg: ModelConfig):
    """long_500k is skipped for pure full-attention archs (quadratic attention
    and a >100 TB KV cache at 524k are not deployable — DESIGN.md §4)."""
    return tuple(
        s for s in LM_SHAPES if s.name != "long_500k" or _sub_quadratic(cfg)
    )


ARCHS: Dict[str, ModelConfig] = {}


def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests (per the assignment:
    small layers/width, few experts, tiny vocab; one fwd/train step).

    A config whose attention width differs from its model width
    (``n_heads * head_dim != d_model``, e.g. mistral-nemo-12b's 4096 against
    5120) stays so: d_model 80 against 4 heads of 16, so ``wq``/``wo`` and
    the fused QKV product keep their non-square shapes."""
    non_square = bool(cfg.n_heads and cfg.head_dim
                      and cfg.n_heads * cfg.head_dim != cfg.d_model)
    changes: dict = dict(
        n_layers=cfg.period if cfg.period > 1 else 2,
        d_model=80 if non_square else 64,
        vocab=97,
        param_dtype="float32",
        compute_dtype="float32",
        remat=False,
    )
    if cfg.n_heads:
        changes.update(n_heads=4, n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads else 4)
        changes["head_dim"] = 32 if cfg.mrope_sections else 16
    if cfg.mrope_sections:
        changes["mrope_sections"] = (4, 6, 6)  # sums to head_dim/2 = 16
    if cfg.d_ff:
        changes["d_ff"] = 128
    if cfg.n_experts:
        changes.update(n_experts=6, top_k=2, moe_d_ff=32)
        if cfg.n_shared_experts:
            changes["n_shared_experts"] = 2
    if cfg.ssm_state:
        changes.update(ssm_state=16, ssm_head_dim=8, ssm_chunk=8)
    return dataclasses.replace(cfg, **changes)


def register(cfg: ModelConfig) -> ModelConfig:
    ARCHS[cfg.name] = cfg
    return cfg


def get(name: str) -> ModelConfig:
    return ARCHS[name]


def _load_all() -> None:
    from repro.configs import (  # noqa: F401
        jamba_1_5_large_398b,
        mamba2_780m,
        minitron_8b,
        mistral_nemo_12b,
        moonshot_v1_16b_a3b,
        musicgen_large,
        phi3_medium_14b,
        qwen2_moe_a2_7b,
        qwen2_vl_72b,
        qwen3_8b,
    )


_load_all()

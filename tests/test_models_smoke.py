"""Per-architecture smoke tests (assignment requirement): reduced same-family
configs, one forward + one train step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke, shapes_for
from repro.models.model import (
    count_active_params,
    count_params,
    forward,
    init_model,
)
from repro.train.trainer import TrainConfig, init_state, make_train_step

KEY = jax.random.key(0)

# Default (fast) runs smoke one arch per mixer family; the full per-arch
# sweep rides behind `-m slow` (multi-second jit compiles per config).
REPRESENTATIVE = {"qwen3-8b", "mamba2-780m", "qwen2-moe-a2.7b"}
ARCH_PARAMS = [
    name if name in REPRESENTATIVE
    else pytest.param(name, marks=pytest.mark.slow)
    for name in sorted(ARCHS)
]


def _inputs(cfg, b=2, t=16):
    if cfg.modality == "text":
        return jax.random.randint(KEY, (b, t), 0, cfg.vocab)
    return jax.random.normal(KEY, (b, t, cfg.d_model), dtype=jnp.float32)


@pytest.mark.parametrize("name", ARCH_PARAMS)
def test_forward_smoke(name):
    cfg = reduce_for_smoke(ARCHS[name])
    params = init_model(KEY, cfg)
    x = _inputs(cfg)
    logits, _ = forward(params, x, cfg)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


# Train-step smokes pay a bigger jit bill; default runs one arch, the rest
# ride behind -m slow.
TRAIN_ARCH_PARAMS = [
    name if name == "qwen3-8b" else pytest.param(name, marks=pytest.mark.slow)
    for name in sorted(ARCHS)
]


@pytest.mark.parametrize("name", TRAIN_ARCH_PARAMS)
def test_train_step_smoke(name):
    cfg = reduce_for_smoke(ARCHS[name])
    state = init_state(KEY, cfg)
    step = jax.jit(make_train_step(cfg, TrainConfig(total_steps=10)))
    batch = {
        "inputs": _inputs(cfg),
        "labels": jax.random.randint(KEY, (2, 16), 0, cfg.vocab),
    }
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["skipped"]) == 0.0
    gn = float(metrics["grad_norm"])
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_full_config_abstract(name):
    """FULL configs are exercised abstractly (eval_shape — no allocation):
    parameter counts in the expected band for each published size."""
    cfg = ARCHS[name]
    n = count_params(cfg)
    expected = {
        # total params incl. embeddings (untied), from the published configs
        "musicgen-large": (1.0e9, 3.0e9),
        "qwen2-vl-72b": (65e9, 80e9),
        "phi3-medium-14b": (12e9, 16e9),
        "mistral-nemo-12b": (11e9, 14e9),
        "minitron-8b": (7e9, 10.5e9),
        "qwen3-8b": (7e9, 10e9),
        "mamba2-780m": (0.6e9, 1.0e9),
        "qwen2-moe-a2.7b": (13e9, 16.5e9),   # 14.3B total / ~2.7B active
        # the assignment's 48L config (implemented verbatim) is larger than
        # the published 27L Moonlight-16B; active stays ~3-4B ("a3b")
        "moonshot-v1-16b-a3b": (26e9, 30e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
    }[name]
    assert expected[0] < n < expected[1], f"{name}: {n/1e9:.2f}B params"
    a = count_active_params(cfg)
    assert a <= n
    if cfg.n_experts:
        assert a < 0.6 * n  # MoE: active ≪ total


def test_moe_active_params_sane():
    cfg = ARCHS["qwen2-moe-a2.7b"]
    a = count_active_params(cfg)
    assert 2.0e9 < a < 4.5e9  # “A2.7B”


def test_long_500k_skip_policy():
    """long_500k runs only for sub-quadratic mixers (DESIGN.md §4)."""
    for name, cfg in ARCHS.items():
        names = [s.name for s in shapes_for(cfg)]
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in names, name
        else:
            assert "long_500k" not in names, name
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(names)


def test_total_cells_count():
    total = sum(len(shapes_for(c)) for c in ARCHS.values())
    assert total == 32  # 10×3 + 2 long_500k (8 full-attention skips noted)

"""The engine's ``mode="auto"`` policy: one rule picks the backend from the
activation rows, the contraction and whether the artifact carries LUTs; the
freeze planner applies the same rule; unknown modes fail loudly.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.da import DAConfig
from repro.core.engine import (
    canonical_mode,
    da_matmul,
    get_backend,
    lut_cells,
    pack_quantized,
    pack_weights,
    registered_backends,
    select_backend,
)
from repro.core.freeze import plan_layer

#: One representative (M, K, N) per regime: decode-like (M ≤ 8), mid and big
#: rows against small (K·N ≤ 2^14), mid (≤ 2^20) and large weight matrices.
SHAPES = {
    "dec:s": (4, 64, 128),
    "dec:m": (4, 512, 1024),
    "dec:l": (4, 2048, 2048),
    "mid:s": (64, 64, 128),
    "mid:m": (64, 512, 1024),
    "mid:l": (64, 2048, 2048),
    "big:s": (512, 64, 128),
    "big:m": (512, 512, 1024),
    "big:l": (512, 2048, 2048),
}


@pytest.mark.parametrize("has_luts", [True, False])
@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_auto_returns_registered_backend_for_every_bucket(cell, has_luts):
    """The rule yields a registered, eligible DA backend for every
    representative shape, with and without LUTs."""
    m, k, n = SHAPES[cell]
    cfg = DAConfig(x_signed=True)
    name = select_backend(m, k, n, cfg, has_luts=has_luts)
    spec = registered_backends()[name]
    assert spec.is_da and spec.supports(cfg, has_luts)


@pytest.mark.parametrize("luts_fit", [True, False])
@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_freeze_plan_matches_dispatch(cell, luts_fit):
    """The freeze planner and call-time dispatch pick the same backend: a
    layer planned at m_hint=M serves under the backend an M-row call
    against the packed artifact would dispatch to, LUTs fitting or not."""
    m, k, n = SHAPES[cell]
    cfg = DAConfig(x_signed=True)
    cells = lut_cells(k, n, cfg.group_size)
    plan = plan_layer(k, n, cfg, m_hint=m,
                      lut_cell_limit=cells if luts_fit else cells - 1)
    assert plan.with_luts == luts_fit and plan.source == "rule"
    assert plan.mode == select_backend(m, k, n, cfg, has_luts=luts_fit)
    assert plan.mode == ("lut" if luts_fit and m <= 8 else "bitplane_stacked")


def test_unknown_mode_rejected_with_clear_error():
    rng = np.random.default_rng(0)
    w = rng.integers(-128, 128, (16, 8)).astype(np.int32)
    packed = pack_quantized(w, cfg=DAConfig(x_signed=True))
    x = jnp.asarray(rng.normal(size=(2, 16)), dtype=jnp.float32)
    with pytest.raises(ValueError, match="unknown DA mode 'warp'"):
        da_matmul(x, packed, mode="warp")
    with pytest.raises(ValueError, match="registered backends"):
        get_backend("warp9")


def test_legacy_mode_aliases_canonicalize():
    assert canonical_mode("da_lut") == "lut"
    assert canonical_mode("da_bitplane") == "bitplane"
    assert canonical_mode("da_bitplane_stacked") == "bitplane_stacked"
    assert get_backend("da_lut").name == "lut"


def test_auto_dispatch_end_to_end_matches_explicit():
    """mode='auto' (the surface serve/engine.py and core/linear.py use)
    produces the same integers as every explicit backend, whatever it picks."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3, 32)).astype(np.float32)
    w = rng.normal(size=(32, 16)).astype(np.float32)
    packed = pack_weights(jnp.asarray(w))  # mode defaults to "auto"
    y_auto = np.asarray(packed(jnp.asarray(x)))
    y_exp = np.asarray(da_matmul(jnp.asarray(x), packed, mode="bitplane"))
    np.testing.assert_array_equal(y_auto, y_exp)


def test_packed_auto_respects_lut_cell_limit():
    """pack_weights(mode='auto'): LUTs built only when they fit the budget,
    and dispatch adapts (no LUTs → storage-free backend)."""
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(64, 32)), dtype=jnp.float32)
    small = pack_weights(w)                      # 2^8/8 × 2048 cells: fits
    tight = pack_weights(w, lut_cell_limit=100)       # budget too small
    assert small.has_luts and not tight.has_luts
    cfg = DAConfig(x_signed=True)
    assert select_backend(4, 64, 32, cfg, small.has_luts) == "lut"
    chosen = select_backend(4, 64, 32, cfg, tight.has_luts)
    assert not registered_backends()[chosen].needs_luts


def test_explicit_mode_enforces_capabilities():
    """An explicit mode that violates its capability spec errors instead of
    silently computing wrong integers (int8 wraps unsigned codes ≥ 128)."""
    from repro.core.engine import da_vmm as engine_da_vmm

    rng = np.random.default_rng(2)
    w = rng.integers(-128, 128, (16, 8)).astype(np.int32)
    ucfg = DAConfig(x_signed=False)
    packed = pack_quantized(w, cfg=ucfg)
    x = jnp.asarray(rng.integers(0, 256, (2, 16)), dtype=jnp.int32)
    with pytest.raises(ValueError, match="signed"):
        engine_da_vmm(x, packed, mode="int8", cfg=ucfg)


def test_explicit_auto_overrides_packed_mode():
    """mode='auto' at the call site runs shape dispatch even on an artifact
    packed with a concrete default mode; mode=None defers to the artifact.
    (Outputs are bit-identical either way — that's the engine's invariant —
    so the dispatch target is asserted on the resolver.)"""
    from repro.core.engine import _resolve_spec

    cfg = DAConfig(x_signed=True)
    # 16 rows: the rule runs stacked bit-planes even though LUTs exist
    auto = _resolve_spec("auto", 16, 32, 16, cfg, True, default_mode="lut")
    assert auto.name == "bitplane_stacked"  # the rule's pick, not the default
    deferred = _resolve_spec(None, 16, 32, 16, cfg, True, default_mode="lut")
    assert deferred.name == "lut"
    # and the float path accepts both spellings end-to-end
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(32, 16)), dtype=jnp.float32)
    packed = pack_weights(w, mode="lut")
    x = jnp.asarray(rng.normal(size=(16, 32)), dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(da_matmul(x, packed, mode="auto")),
        np.asarray(da_matmul(x, packed)),
    )

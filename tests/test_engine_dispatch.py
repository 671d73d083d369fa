"""The engine's shape-aware ``mode="auto"`` dispatch: every shape bucket
resolves to a registered, eligible backend; a missing autotune cache degrades
to the deterministic heuristic; unknown modes fail loudly.
"""
import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.da import DAConfig
from repro.core.engine import (
    BUCKET_SHAPES,
    canonical_mode,
    da_matmul,
    get_backend,
    load_cost_table,
    pack_quantized,
    pack_weights,
    registered_backends,
    select_backend,
    set_cost_table,
    shape_bucket,
)


@pytest.fixture(autouse=True)
def _isolate_cost_table():
    """Each test installs its own cost table; restore lazy state afterwards."""
    yield
    set_cost_table(None)


def test_bucketing_is_total_and_stable():
    """shape_bucket covers all of (M, K, N, bits) space and its 9 cells match
    the representative shapes the autotune benchmark times."""
    cfg_bits = 8
    seen = set()
    for m in (1, 8, 9, 256, 257, 4096):
        for k, n in ((8, 8), (128, 128), (512, 512), (4096, 4096)):
            b = shape_bucket(m, k, n, cfg_bits)
            mb, kb, bits = b.split(":")
            assert mb in {"dec", "mid", "big"} and kb in {"s", "m", "l"}
            assert bits == f"b{cfg_bits}"
            seen.add(b)
    assert len(seen) == 9
    assert seen == {
        shape_bucket(m, k, n, cfg_bits) for m, k, n in BUCKET_SHAPES.values()
    }


@pytest.mark.parametrize("has_luts", [True, False])
@pytest.mark.parametrize("cell", sorted(BUCKET_SHAPES))
def test_auto_returns_registered_backend_for_every_bucket(cell, has_luts):
    """No cache: the fallback policy yields a registered, eligible backend
    for every shape bucket, with and without LUTs."""
    set_cost_table({})  # simulate absent autotune cache
    m, k, n = BUCKET_SHAPES[cell]
    cfg = DAConfig(x_signed=True)
    name = select_backend(m, k, n, cfg, has_luts=has_luts)
    spec = registered_backends()[name]
    assert spec.is_da and spec.supports(cfg, has_luts)


def test_auto_follows_measured_costs():
    """With a cost table present, auto picks the cheapest eligible backend —
    and ignores measurements for ineligible ones (LUT modes without LUTs)."""
    cfg = DAConfig(x_signed=True)
    bucket = shape_bucket(4, 64, 128, cfg.x_bits)
    set_cost_table({bucket: {"onehot": 1.0, "bitplane": 5.0, "int8": 0.1}})
    # int8 is measured cheapest but is not a DA backend: never auto-picked
    assert select_backend(4, 64, 128, cfg, has_luts=True) == "onehot"
    # without LUTs the measured winner is ineligible → next eligible measured
    assert select_backend(4, 64, 128, cfg, has_luts=False) == "bitplane"


def test_auto_fallback_when_bucket_unmeasured():
    """A cache that lacks the bucket behaves exactly like no cache."""
    cfg = DAConfig(x_signed=True)
    other = shape_bucket(512, 2048, 2048, cfg.x_bits)
    set_cost_table({other: {"bitplane": 1.0}})
    with_table = select_backend(4, 64, 128, cfg, has_luts=True)
    set_cost_table({})
    without = select_backend(4, 64, 128, cfg, has_luts=True)
    assert with_table == without


def test_bucket_miss_warns_once_per_bucket_and_backend():
    """A tuned cache that misses the dispatched bucket warns ONCE per
    (bucket, fallback backend) — a decode loop hits the same bucket every
    token and must not spam — while a wholly absent cache stays silent."""
    import warnings as _warnings

    cfg = DAConfig(x_signed=True)
    other = shape_bucket(512, 2048, 2048, cfg.x_bits)
    set_cost_table({other: {"bitplane": 1.0}})
    with pytest.warns(UserWarning, match="no timings"):
        first = select_backend(4, 64, 128, cfg, has_luts=True)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")  # any repeat warning would raise
        assert select_backend(4, 64, 128, cfg, has_luts=True) == first
        # a different bucket gets its own single warning
        with pytest.warns(UserWarning, match="no timings"):
            select_backend(300, 64, 128, cfg, has_luts=True)
    # installing a fresh table resets the dedup set
    set_cost_table({other: {"bitplane": 1.0}})
    with pytest.warns(UserWarning, match="no timings"):
        select_backend(4, 64, 128, cfg, has_luts=True)
    # no cache at all → heuristic silently (the engine never requires tuning)
    set_cost_table({})
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        select_backend(4, 64, 128, cfg, has_luts=True)


def test_cost_table_loads_from_json(tmp_path):
    """The autotune JSON cache round-trips through the loader; junk entries
    (unknown backends, malformed costs) are dropped, not fatal."""
    cfg = DAConfig(x_signed=True)
    bucket = shape_bucket(4, 64, 128, cfg.x_bits)
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps({
        "version": 1, "device": "cpu",
        "table": {bucket: {"lut": 2.0, "bitplane_stacked": 9.0,
                           "not_a_backend": 1e-9, "bitplane": "junk"}},
    }))
    table = load_cost_table(p)
    assert table[bucket] == {"lut": 2.0, "bitplane_stacked": 9.0}
    set_cost_table(table)
    assert select_backend(4, 64, 128, cfg, has_luts=True) == "lut"


def test_cost_table_absent_or_corrupt_is_safe(tmp_path):
    """Missing and corrupt caches degrade to {} — dispatch still works."""
    assert load_cost_table(tmp_path / "nope.json") == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_cost_table(bad) == {}
    set_cost_table({})
    assert select_backend(1, 16, 16, DAConfig(x_signed=True), True)


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
    set_cost_table({})
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
    set_cost_table({})
    cfg = DAConfig(x_signed=True)
    assert select_backend(4, 64, 32, cfg, small.has_luts) == "lut"
    chosen = select_backend(4, 64, 32, cfg, tight.has_luts)
    assert not registered_backends()[chosen].needs_luts


def test_engine_default_cache_path_env(monkeypatch, tmp_path):
    p = tmp_path / "alt.json"
    monkeypatch.setenv("REPRO_ENGINE_AUTOTUNE", str(p))
    assert engine.default_cache_path() == p


def test_explicit_path_load_is_read_only(tmp_path):
    """load_cost_table(path) inspects without redirecting auto dispatch —
    only default-path loads (or set_cost_table) touch the process table."""
    installed = {"some:bucket:b8": {"bitplane": 1.0}}
    set_cost_table(installed)
    p = tmp_path / "other.json"
    p.write_text(json.dumps({"device": "cpu", "table": {}}))
    assert load_cost_table(p) == {}
    assert load_cost_table() == installed  # process table untouched


def test_cost_table_registry_fingerprint_mismatch_warns(tmp_path):
    """A cache tuned against a different backend registry (renamed/added/
    removed backends) is ignored with a warning — the heuristic fallback
    serves dispatch instead of stale rankings or a KeyError."""
    import jax

    from repro.core.engine import registry_fingerprint

    cfg = DAConfig(x_signed=True)
    bucket = shape_bucket(4, 64, 128, cfg.x_bits)
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "version": 1, "device": jax.default_backend(),
        "registry": "00000000", "table": {bucket: {"lut": 1.0}},
    }))
    with pytest.warns(UserWarning, match="different backend registry"):
        assert load_cost_table(stale) == {}
    # a matching fingerprint loads normally; absence of a stamp is accepted
    # (pre-fingerprint caches keep working)
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({
        "version": 1, "device": jax.default_backend(),
        "registry": registry_fingerprint(), "table": {bucket: {"lut": 1.0}},
    }))
    assert load_cost_table(fresh) == {bucket: {"lut": 1.0}}


def test_cost_table_unknown_backend_names_warn(tmp_path):
    """Unknown backend names in a cache are dropped with a warning, and
    dispatch still resolves (heuristic covers untimed shapes)."""
    import jax

    cfg = DAConfig(x_signed=True)
    bucket = shape_bucket(4, 64, 128, cfg.x_bits)
    p = tmp_path / "renamed.json"
    p.write_text(json.dumps({
        "version": 1, "device": jax.default_backend(),
        "table": {bucket: {"warp_drive": 0.1, "lut": 2.0}},
    }))
    with pytest.warns(UserWarning, match="unregistered backends"):
        table = load_cost_table(p)
    assert table[bucket] == {"lut": 2.0}
    set_cost_table(table)
    assert select_backend(4, 64, 128, cfg, has_luts=True) == "lut"


def test_cost_table_rejects_other_device(tmp_path):
    """A cache tuned on different hardware must not steer dispatch (a
    TPU-tuned table would send CPU through interpret-mode Pallas)."""
    import jax

    cfg = DAConfig(x_signed=True)
    bucket = shape_bucket(4, 64, 128, cfg.x_bits)
    p = tmp_path / "tuned_elsewhere.json"
    other = "tpu" if jax.default_backend() != "tpu" else "cpu"
    p.write_text(json.dumps(
        {"version": 1, "device": other, "table": {bucket: {"pallas_lut": 0.1}}}
    ))
    here = jax.default_backend()
    with pytest.warns(UserWarning,
                      match=f"tuned on '{other}' but this process runs on "
                            f"'{here}'"):
        assert load_cost_table(p) == {}
    with warnings.catch_warnings():  # once per cache and device, not per load
        warnings.simplefilter("error")
        assert load_cost_table(p) == {}


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
    bucket = shape_bucket(3, 32, 16, cfg.x_bits)
    set_cost_table({bucket: {"bitplane_stacked": 1.0, "lut": 50.0}})
    auto = _resolve_spec("auto", 3, 32, 16, cfg, True, default_mode="lut")
    assert auto.name == "bitplane_stacked"  # measured winner, not the default
    deferred = _resolve_spec(None, 3, 32, 16, cfg, True, default_mode="lut")
    assert deferred.name == "lut"
    # and the float path accepts both spellings end-to-end
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(32, 16)), dtype=jnp.float32)
    packed = pack_weights(w, mode="lut")
    x = jnp.asarray(rng.normal(size=(3, 32)), dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(da_matmul(x, packed, mode="auto")),
        np.asarray(da_matmul(x, packed)),
    )

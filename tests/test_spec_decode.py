"""Speculative decoding: acceptance math, partial-bits engine evaluation,
token identity for all three draft providers, page-leak freedom, and the
acceptance-EMA auto-disable.

Fast lane: gamma <= 2 on the smoke model (the nightly benchmark exercises
production-shaped gammas and model sizes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.core import engine
from repro.core.da import DAConfig, truncate_codes
from repro.core.engine import da_matmul, da_vmm, pack_quantized, pack_weights
from repro.models.model import init_model
from repro.serve.engine import Request, ServeEngine
from repro.spec import SpecConfig, breakeven_acceptance, greedy_accept

KEY = jax.random.key(0)
MAX_NEW = 4


# ---------------------------------------------------------------------------
# acceptance math (pure)
# ---------------------------------------------------------------------------
def test_greedy_accept_prefix_rules():
    # no drafts match → only the correction token
    assert greedy_accept([5, 6], [1, 2, 3]) == 1
    # first matches, second diverges → matched prefix + correction
    assert greedy_accept([1, 6], [1, 2, 3]) == 2
    # all match → everything + the bonus token
    assert greedy_accept([1, 2], [1, 2, 3]) == 3
    # a later "match" after a divergence never counts (prefix semantics)
    assert greedy_accept([9, 2], [1, 2, 3]) == 1
    with pytest.raises(ValueError):
        greedy_accept([1, 2], [1, 2])  # window must cover drafts + 1


def test_breakeven_is_cost_ratio():
    assert breakeven_acceptance(4, 0.5) == 0.5
    assert breakeven_acceptance(8, 1.5) == 1.0
    assert breakeven_acceptance(2, -1.0) == 0.0


# ---------------------------------------------------------------------------
# partial-bits evaluation in the engine (the DA-native draft pass)
# ---------------------------------------------------------------------------
def test_truncate_codes_is_low_bit_masking():
    cfg = DAConfig(x_signed=True)
    xq = jnp.asarray(np.random.default_rng(0).integers(-128, 128, (4, 16)),
                     dtype=jnp.int32)
    for eff in (8, 5, 2, 1):
        shifted, ecfg, drop = truncate_codes(xq, cfg, eff)
        assert ecfg.x_bits == eff and drop == 8 - eff
        mask = ~((1 << drop) - 1)
        np.testing.assert_array_equal(
            np.asarray(shifted) << drop, np.asarray(xq) & mask)
    with pytest.raises(ValueError):
        truncate_codes(xq, cfg, 0)
    with pytest.raises(ValueError):
        truncate_codes(xq, cfg, 9)


@pytest.mark.parametrize("mode", ["lut", "onehot", "bitplane",
                                  "bitplane_stacked"])
def test_da_vmm_partial_bits_equals_masked_codes(mode, rng):
    """Every backend's x_bits_eff evaluation == the exact product of the
    low-bit-masked codes (the top-plane partial sum, bit-exactly)."""
    cfg = DAConfig(x_signed=True)
    w = rng.integers(-128, 128, (24, 8)).astype(np.int32)
    packed = pack_quantized(w, cfg=cfg)
    xq = jnp.asarray(rng.integers(-128, 128, (3, 24)), dtype=jnp.int32)
    for eff in (8, 4, 2):
        y = np.asarray(da_vmm(xq, packed, mode=mode, cfg=cfg, x_bits_eff=eff))
        ref = (np.asarray(xq) & ~((1 << (8 - eff)) - 1)) @ w
        np.testing.assert_array_equal(y, ref)


def test_da_matmul_x_bits_eff_and_override_context(rng):
    w = jnp.asarray(rng.normal(size=(32, 16)), dtype=jnp.float32)
    packed = pack_weights(w)
    x = jnp.asarray(rng.normal(size=(3, 32)), dtype=jnp.float32)
    y_full = np.asarray(da_matmul(x, packed))
    # eff == x_bits is exactly the full evaluation
    np.testing.assert_array_equal(
        y_full, np.asarray(da_matmul(x, packed, x_bits_eff=8)))
    y4 = np.asarray(da_matmul(x, packed, x_bits_eff=4))
    assert not np.array_equal(y4, y_full)  # genuinely truncated
    # the trace-time override context drives calls with no explicit arg
    with engine.x_bits_override(4):
        np.testing.assert_array_equal(
            y4, np.asarray(jax.jit(lambda a: da_matmul(a, packed))(x)))
    # and full precision is restored outside the context
    np.testing.assert_array_equal(y_full, np.asarray(da_matmul(x, packed)))


# ---------------------------------------------------------------------------
# serving: token identity + leak freedom for all three providers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                              moe_dropless=True)
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(7)
    prompts = {uid: rng.integers(0, cfg.vocab, 3 + uid) for uid in range(4)}

    from repro.core.freeze import freeze_model

    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    return cfg, params, art, prompts


def _serve(cfg, params, prompts, spec, **kw):
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=4,
                      spec=spec, **kw)
    for uid, pr in prompts.items():
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
    done = eng.run()
    return {u: r.generated for u, r in done.items()}, eng.metrics()


@pytest.mark.parametrize("provider", ["bitplane", "layerskip", "artifact"])
def test_spec_decode_token_identical_and_leak_free(setup, provider):
    """Acceptance: greedy spec decode emits EXACTLY the tokens of
    non-speculative greedy decode on the same frozen artifact, for every
    draft provider, and finishes with zero pages held."""
    cfg, params, art, prompts = setup
    if provider == "layerskip":
        serve_params, spec = params, SpecConfig(
            provider="layerskip", gamma=2, disable_below=0.0)
    elif provider == "artifact":
        dcfg = dataclasses.replace(cfg, n_layers=1, name="draft")
        spec = SpecConfig(provider="artifact", gamma=2,
                          draft_params=init_model(jax.random.key(1), dcfg),
                          draft_model_cfg=dcfg, disable_below=0.0)
        serve_params = art.params
    else:
        serve_params, spec = art.params, SpecConfig(
            provider="bitplane", gamma=2, draft_x_bits=6, disable_below=0.0)
    base, _ = _serve(cfg, serve_params, prompts, None)
    out, m = _serve(cfg, serve_params, prompts, spec)
    assert out == base
    assert m["spec"]["rounds"] > 0  # speculation actually ran
    assert m["spec"]["provider"] == provider
    assert m["pool"]["used_pages"] == 0  # rejected drafts leaked nothing


def test_spec_acceptance_ema_auto_disable(setup):
    """A drafter whose proposals never survive verification must be switched
    off per-request by the acceptance-EMA floor — and the output is still
    exactly the baseline (disable changes effort, never tokens)."""
    cfg, _, art, prompts = setup
    base, _ = _serve(cfg, art.params, prompts, None)
    # 1-bit drafts are noise on this model → acceptance ~0 → disable
    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=1,
                      warmup_rounds=1)
    out, m = _serve(cfg, art.params, prompts, spec)
    assert out == base
    assert m["spec"]["disabled_requests"] >= 1
    assert m["spec"]["enabled_requests"] < len(prompts)
    assert m["spec"]["acceptance_rate"] < m["spec"]["disable_floor"]


def test_spec_metrics_surface_in_scheduler(setup):
    cfg, _, art, prompts = setup
    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=6,
                      disable_below=0.0)
    _, m = _serve(cfg, art.params, prompts, spec)
    s = m["spec"]
    for key in ("acceptance_rate", "draft_steps", "verify_steps", "rounds",
                "drafted_tokens", "accepted_drafts", "disabled_requests",
                "enabled_requests", "cost_ratio", "gamma"):
        assert key in s, key
    # draft_steps counts single-token draft forwards (gamma per fused device
    # call), verify_steps counts verify calls, rounds counts lane-rounds
    # (several lanes share one batched call)
    assert s["draft_steps"] == s["gamma"] * s["verify_steps"]
    assert s["drafted_tokens"] == s["gamma"] * s["rounds"]
    assert s["rounds"] >= s["verify_steps"] > 0
    # a non-speculative engine reports spec=None (on/off state is explicit)
    _, m0 = _serve(cfg, art.params, prompts, None)
    assert m0["spec"] is None


def test_artifact_draft_survives_defrag_and_chunked_catch_up(setup):
    """Regression (review findings): the artifact drafter's own pools must
    move under the SAME remap as the target pools when defrag renumbers
    pages, and a long un-ingested context is caught up in
    prefill_chunk-bucketed slices — tokens stay exactly the baseline's
    through both."""
    cfg, _, art, prompts = setup
    dcfg = dataclasses.replace(cfg, n_layers=1, name="draft")
    spec = SpecConfig(provider="artifact", gamma=2,
                      draft_params=init_model(jax.random.key(1), dcfg),
                      draft_model_cfg=dcfg, disable_below=0.0)
    kw = dict(batch_size=2, max_len=32, page_size=4, prefill_chunk=4)
    base = {}
    for with_spec in (None, spec):
        eng = ServeEngine(cfg, art.params, spec=with_spec, **kw)
        for uid, pr in prompts.items():  # prompts up to 6 > chunk → catch-up
            eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
        for _ in range(3):
            eng.step()
        eng._rt.defrag()  # pages renumber; draft pools must move along
        done = eng.run()
        base[with_spec is None] = {u: r.generated for u, r in done.items()}
        assert eng.metrics()["pool"]["used_pages"] == 0
    assert base[False] == base[True]


def test_spec_config_and_engine_validation(setup):
    cfg, params, art, _ = setup
    with pytest.raises(ValueError, match="gamma"):
        SpecConfig(gamma=0)
    with pytest.raises(ValueError, match="greedy"):
        ServeEngine(cfg, art.params, batch_size=2, max_len=32, greedy=False,
                    spec=SpecConfig(provider="bitplane"))
    with pytest.raises(ValueError, match="paged runtime"):
        ServeEngine(cfg, art.params, batch_size=2, max_len=32,
                    runtime="slots", spec="bitplane")
    with pytest.raises(ValueError, match="bit-planes"):
        # float params have no bit-planes to truncate
        ServeEngine(cfg, params, batch_size=2, max_len=32, spec="bitplane")
    with pytest.raises(ValueError, match="unknown draft provider"):
        ServeEngine(cfg, art.params, batch_size=2, max_len=32,
                    spec=SpecConfig(provider="telepathy"))
    with pytest.raises(ValueError, match="draft_artifact"):
        ServeEngine(cfg, art.params, batch_size=2, max_len=32,
                    spec=SpecConfig(provider="artifact"))

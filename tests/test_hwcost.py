"""Hardware cost observability: the per-layer cost table agrees exactly with
the calibrated hwmodel (Table I untouched), truncated-bitplane repricing is
exactly linear in the evaluated planes, the table round-trips through the
artifact manifest, and — the serving acceptance property — the scheduler's
attributed energy sums EXACTLY to per-layer pJ × executed tokens."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.core.da import DAConfig
from repro.core.freeze import freeze_model, load_artifact, save_artifact
from repro.core.hwmodel import BitSliceDesign, DADesign, PJ
from repro.models.model import init_model
from repro.obs import check as obs_check
from repro.obs import regress as obs_regress
from repro.obs.export import validate_chrome_trace, validate_metrics_json
from repro.obs.hwcost import (
    HWCOST_VERSION,
    HardwareCostModel,
    draft_price,
)
from repro.obs.metrics import METRICS_SCHEMA_VERSION
from repro.serve.engine import Request, ServeEngine
from repro.spec import SpecConfig

CONV1 = [("conv1", 25, 6)]
MAX_NEW = 4


# ---------------------------------------------------------------------------
# cost table vs the calibrated hwmodel (pure)
# ---------------------------------------------------------------------------
def test_conv1_matches_table1_exactly():
    """The table prices the paper's design point identically to the
    calibration tests in test_hwmodel — same model, lifted, not re-derived."""
    hw = HardwareCostModel.from_shapes(CONV1)
    assert hw.pj_per_token() == pytest.approx(110.2, rel=1e-6)
    assert hw.ns_per_token() == pytest.approx(88.0)
    assert hw.bitslice_pj_per_token() == pytest.approx(1421.5, rel=1e-6)
    assert hw.bitslice_ns_per_token() == pytest.approx(400.0)
    r = hw.ratios()
    assert r["energy"] == pytest.approx(1421.5 / 110.2, rel=1e-6)
    assert r["latency"] == pytest.approx(400.0 / 88.0, rel=1e-6)
    # the acceptance headline: ≥10× energy on CONV1-class geometry
    assert r["energy"] > 10.0


def test_components_sum_to_total_exactly():
    hw = HardwareCostModel.from_shapes(CONV1)
    assert sum(hw.components().values()) == hw.pj_per_token()
    assert sum(hw.bitslice_components().values()) == \
        hw.bitslice_pj_per_token()
    # and the component split is the hwmodel's own, in pJ
    d = DADesign(k=25, n=6)
    for key, joules in d.energy_components_j().items():
        assert hw.components()[f"{key}_pj"] == pytest.approx(joules / PJ)
    b = BitSliceDesign(k=25, n=6)
    for key, joules in b.energy_components_j().items():
        assert hw.bitslice_components()[f"{key}_pj"] == \
            pytest.approx(joules / PJ)


def test_vmms_per_token_stacks_linearly():
    one = HardwareCostModel.from_shapes(CONV1)
    three = HardwareCostModel.from_shapes([("conv1", 25, 6, 3)])
    assert three.pj_per_token() == pytest.approx(3 * one.pj_per_token())
    assert three.ns_per_token() == pytest.approx(3 * one.ns_per_token())
    row = three.layer_table()[0]
    assert row["vmms_per_token"] == 3
    assert row["memory_cells"] == 3 * one.layer_table()[0]["memory_cells"]


def test_x_bits_eff_exactly_linear():
    """A truncated-bitplane pass runs the SAME circuits for fewer bit-serial
    cycles: energy scales by eff/x_bits EXACTLY on every component, and
    latency drops by the skipped read cycles (CONV1: 15 + 3·10 + 3)."""
    hw = HardwareCostModel.from_shapes(CONV1)
    assert hw.pj_per_token(x_bits_eff=4) == 0.5 * hw.pj_per_token()
    for key, full in hw.components().items():
        assert hw.components(x_bits_eff=4)[key] == 0.5 * full
    assert hw.ns_per_token(x_bits_eff=4) == pytest.approx(48.0)
    # the counterfactual scales too (fewer DAC/input cycles) — the live
    # energy ratio is therefore invariant under draft truncation
    assert hw.bitslice_pj_per_token(x_bits_eff=4) == \
        0.5 * hw.bitslice_pj_per_token()
    assert hw.ratios(x_bits_eff=4)["energy"] == \
        pytest.approx(hw.ratios()["energy"])
    # clamped to [1, x_bits]
    assert hw.pj_per_token(x_bits_eff=99) == hw.pj_per_token()
    assert hw.pj_per_token(x_bits_eff=0) == hw.pj_per_token(x_bits_eff=1)


def test_json_roundtrip_and_version_gate():
    hw = HardwareCostModel.from_shapes(
        [("a", 25, 6), {"path": "b", "k": 64, "n": 32, "vmms_per_token": 2}])
    again = HardwareCostModel.from_json(hw.to_json())
    assert again == hw
    assert again.summary() == hw.summary()
    newer = {"hwcost_version": HWCOST_VERSION + 1, "layers": []}
    with pytest.raises(ValueError):
        HardwareCostModel.from_json(newer)
    assert not HardwareCostModel([])  # empty is falsy → "no cost model"


# ---------------------------------------------------------------------------
# frozen-model construction + artifact round-trip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def frozen():
    cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                              moe_dropless=True)
    params = init_model(jax.random.key(0), cfg)
    art = freeze_model(params, DAConfig(x_signed=True), mode="bitplane",
                       model_cfg=cfg)
    return cfg, art


def test_from_frozen_geometry(frozen):
    cfg, art = frozen
    hw = art.hwcost
    assert hw and len(hw.layers) > 0
    for g in hw.layers:
        assert g.k > 0 and g.n > 0 and g.vmms_per_token >= 1
    # stacked period leaves fold their leading dims into vmms_per_token
    by_path = {g.path: g for g in hw.layers}
    assert any(g.vmms_per_token > 1 for g in hw.layers) or \
        all("periods" not in p for p in by_path)
    # the artifact's table is exactly what from_frozen rebuilds
    assert HardwareCostModel.from_frozen(art.params, art.plan) == hw


def test_artifact_roundtrip_and_pre_hwcost_backcompat(frozen, tmp_path):
    cfg, art = frozen
    d = str(tmp_path / "art")
    save_artifact(d, art)
    loaded = load_artifact(d)
    assert loaded.hwcost == art.hwcost
    # a pre-hwcost artifact (older writer) rebuilds the table from the
    # packed leaves on load — same geometry, same costs
    mpath = tmp_path / "art" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    assert "hwcost" in manifest
    del manifest["hwcost"]
    mpath.write_text(json.dumps(manifest))
    old = load_artifact(d)
    assert old.hwcost == art.hwcost


def test_draft_price_truncated_bitplane(frozen):
    cfg, art = frozen
    hw = art.hwcost

    class P:  # the bitplane provider's cost-relevant surface
        x_bits_eff = 4

    dp = draft_price(hw, P())
    assert dp["x_bits_eff"] == 4
    assert dp["pj"] == pytest.approx(0.5 * hw.pj_per_token())
    assert dp["bs_pj"] == pytest.approx(0.5 * hw.bitslice_pj_per_token())

    class Q:  # layer-skip style: no x_bits_eff, a cost_ratio
        cost_ratio = 0.25

    dq = draft_price(hw, Q())
    assert dq["x_bits_eff"] is None
    assert dq["pj"] == pytest.approx(0.25 * hw.pj_per_token())


# ---------------------------------------------------------------------------
# serving attribution (acceptance)
# ---------------------------------------------------------------------------
def _serve(cfg, art, n_req=4, **kw):
    eng = ServeEngine(cfg, art.params, batch_size=2, max_len=32, page_size=8,
                      **kw)
    rng = np.random.default_rng(7)
    for uid in range(n_req):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, 3 + uid),
                           max_new_tokens=MAX_NEW))
    done = eng.run()
    return eng, {u: r.generated for u, r in done.items()}


def test_greedy_attribution_sums_exactly(frozen):
    """The scheduler's attributed pJ equals the analytic per-token price ×
    executed token-passes — no hidden constants, no double counting."""
    cfg, art = frozen
    eng, out = _serve(cfg, art)
    m = eng.metrics()
    hw = m["hw"]
    assert hw is not None
    toks = hw["tokens"]
    assert toks["prefill"] + toks["decode"] == m["ctx_tokens"]
    price = art.hwcost.pj_per_token()
    assert hw["est_pj"]["total"] == \
        pytest.approx(m["ctx_tokens"] * price, rel=1e-9)
    assert hw["est_ns"]["total"] == \
        pytest.approx(m["ctx_tokens"] * art.hwcost.ns_per_token(), rel=1e-9)
    assert hw["pj_per_out_token"] == \
        pytest.approx(hw["est_pj"]["total"] / m["out_tokens"], rel=1e-9)
    # live counterfactual: same token counts priced on bit-slicing
    assert hw["live"]["bitslice_pj"] == pytest.approx(
        m["ctx_tokens"] * art.hwcost.bitslice_pj_per_token(), rel=1e-9)
    assert hw["live"]["energy_ratio"] == \
        pytest.approx(art.hwcost.ratios()["energy"], rel=1e-9)


def test_spec_draft_attribution(frozen):
    """Draft passes are priced at x_bits_eff (proportionally fewer bit-plane
    cycles); the total decomposes exactly into full-price phases plus
    draft-price phases."""
    cfg, art = frozen
    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=4,
                      disable_below=0.0)
    eng, out = _serve(cfg, art, spec=spec)
    hw = eng.metrics()["hw"]
    assert hw["draft"]["x_bits_eff"] == 4
    full = art.hwcost.pj_per_token()
    draft = art.hwcost.pj_per_token(x_bits_eff=4)
    assert hw["draft"]["pj"] == pytest.approx(draft)
    assert draft == 0.5 * full
    t = hw["tokens"]
    assert t["draft"] > 0 and t["verify"] > 0
    expect = full * (t["prefill"] + t["decode"] + t["verify"]) \
        + draft * (t["draft"] + t.get("draft_ingest", 0))
    assert hw["est_pj"]["total"] == pytest.approx(expect, rel=1e-9)


def test_attribution_identical_tracing_on_off(frozen):
    cfg, art = frozen
    eng_off, out_off = _serve(cfg, art, trace=False)
    eng_on, out_on = _serve(cfg, art, trace=True)
    assert out_on == out_off
    assert eng_on.metrics()["hw"] == eng_off.metrics()["hw"]
    # energy-annotated spans validate (est_pj/est_ns finite, non-negative)
    from repro.obs import chrome_trace

    trace = chrome_trace(eng_on.obs.tracer)
    assert validate_chrome_trace(trace) == []
    assert any("est_pj" in e.get("args", {}) for e in trace["traceEvents"])


def test_float_weights_have_no_hw_block(frozen):
    cfg, _ = frozen
    params = init_model(jax.random.key(1), cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=8)
    assert eng.metrics().get("hw") is None


# ---------------------------------------------------------------------------
# schema validation + CLI gates
# ---------------------------------------------------------------------------
def test_metrics_json_schema_backcompat(frozen, tmp_path):
    cfg, art = frozen
    eng, _ = _serve(cfg, art)
    path = str(tmp_path / "hw.json")
    eng.write_hw_metrics(path)
    obj = json.loads(open(path).read())
    assert obj["metrics_schema_version"] == METRICS_SCHEMA_VERSION
    assert validate_metrics_json(obj) == []
    assert obs_check.main([path]) == 0  # CLI routes metrics JSON by content
    # v1 files predate the hw block: no hw requirements
    assert validate_metrics_json({"metrics_schema_version": 1}) == []
    # v2 with a null hw block is a schema violation
    errs = validate_metrics_json(
        {"metrics_schema_version": 2, "hw": None})
    assert errs and "hw" in errs[0]
    # files from a newer build fail loudly, never silently half-validate
    assert validate_metrics_json(
        {"metrics_schema_version": METRICS_SCHEMA_VERSION + 1})
    # traces with malformed energy args are rejected
    bad = {"traceEvents": [
        {"name": "d", "ph": "X", "ts": 0, "dur": 1, "pid": 1, "tid": 1,
         "args": {"est_pj": -3.0}}]}
    assert any("est_pj" in e for e in validate_chrome_trace(bad))


def test_regress_cli_gate(frozen, tmp_path):
    cfg, art = frozen
    payload = {
        "metrics_schema_version": METRICS_SCHEMA_VERSION,
        "conv1": {"hw": HardwareCostModel.from_shapes(CONV1).summary()},
        "regress_keys": ["conv1.hw.pj_per_token", "conv1.hw.ratios.energy"],
    }
    committed = tmp_path / "committed.json"
    committed.write_text(json.dumps(payload))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(payload))
    assert obs_regress.main([str(fresh), str(committed)]) == 0
    # a drifted load-bearing number is a regression — symmetric band, so an
    # unexplained "improvement" fails too
    drift = json.loads(committed.read_text())
    drift["conv1"]["hw"]["pj_per_token"] *= 2.0
    fresh.write_text(json.dumps(drift))
    assert obs_regress.main([str(fresh), str(committed)]) == 1
    # schema version drift is a schema change, not a noise band
    v1 = json.loads(committed.read_text())
    v1["metrics_schema_version"] = 1
    fresh.write_text(json.dumps(v1))
    assert obs_regress.main([str(fresh), str(committed)]) == 1
    # a payload with no regress_keys and no --key is a usage error
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"metrics_schema_version": 2}))
    assert obs_regress.main([str(bare), str(bare)]) == 2

"""The DA artifact pipeline: plan → pack → serialize → serve.

Covers the model-level planner (per-layer, the engine's backend rule),
bit-exact PackedWeights persistence through the checkpoint layer (crc
verified), and the freeze-once/serve-many end-to-end: an artifact written to
disk and reloaded in a fresh, template-free path (no float weights in scope)
serves greedy decode identically to the in-memory frozen model.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs.registry import ARCHS
from repro.core.da import DAConfig
from repro.core.engine import (
    DEFAULT_LUT_LIMIT,
    PackedWeights,
    da_matmul,
    lut_cells,
)
from repro.core.freeze import (
    DAArtifact,
    LayerPlan,
    da_memory_report,
    freeze_model,
    load_artifact,
    plan_layer,
    plan_model,
    save_artifact,
)

KEY = jax.random.key(0)


def _serve_cfg(**kw):
    """Tiny qwen3-like serving config (d_model 64, lm head 64 x 503)."""
    base = dict(
        name="qwen3-tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab=503, param_dtype="float32",
        compute_dtype="float32", remat=False, moe_dropless=True,
    )
    base.update(kw)
    return dataclasses.replace(ARCHS["qwen3-8b"], **base)


#: Smallest vocabulary whose 64-wide lm head busts the default LUT budget,
#: so the rule serves it with stacked bit-planes while the smaller layers of
#: the same model read their LUTs.
NO_LUT_VOCAB = DEFAULT_LUT_LIMIT // lut_cells(64, 1, 8) + 1


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_plan_layer_follows_the_backend_rule():
    """The planner applies the engine's rule at m_hint: PMA readout for
    decode-like batches when the LUTs fit the budget, stacked bit-planes
    when they do not or when the batch is wider than 8 rows."""
    cfg = DAConfig(x_signed=True)
    with_luts = plan_layer(64, 64, cfg, m_hint=4)
    assert with_luts.source == "rule" and with_luts.mode == "lut"
    assert with_luts.with_luts
    no_luts = plan_layer(64, 64, cfg, m_hint=4, lut_cell_limit=100)
    assert not no_luts.with_luts and no_luts.mode == "bitplane_stacked"
    wide = plan_layer(64, 64, cfg, m_hint=16)
    assert wide.with_luts and wide.mode == "bitplane_stacked"


def test_plan_model_is_per_layer_not_constant():
    """The acceptance property: plans differ across layer shapes (here the
    lm head's LUTs bust a budget the attention matrix's fit)."""
    cfg = _serve_cfg()
    params = jax.tree.map(jnp.asarray, {
        "mixer": {"wq": np.random.default_rng(0).normal(
            size=(2, cfg.d_model, cfg.d_model)).astype(np.float32)},
        "lm_head": {"w": np.random.default_rng(1).normal(
            size=(cfg.d_model, cfg.vocab)).astype(np.float32)},
    })
    plans = plan_model(params, DAConfig(x_signed=True), m_hint=2,
                       lut_cell_limit=lut_cells(cfg.d_model, cfg.d_model, 8))
    assert set(plans) == {"mixer/wq", "lm_head/w"}
    assert plans["mixer/wq"].mode == "lut"
    assert plans["lm_head/w"].mode == "bitplane_stacked"
    assert not plans["lm_head/w"].with_luts


@pytest.mark.parametrize("m_hint", [4, 32])
@pytest.mark.parametrize("arch", ["qwen3-8b", "mistral-nemo-12b"])
def test_benchmark_widths_plan_bitplane_stacked(arch, m_hint):
    """At the benchmark configurations' published widths (full depth, shapes
    only — nothing is allocated) no layer's LUTs fit the default budget, so
    every DA leaf is planned as stacked bit-planes without LUTs."""
    from repro.models.model import init_model

    cfg = ARCHS[arch]
    shapes = jax.eval_shape(lambda: init_model(jax.random.key(0), cfg))
    plans = plan_model(shapes, DAConfig(x_signed=True), m_hint=m_hint)
    assert plans
    for key, plan in plans.items():
        assert (plan.mode, plan.with_luts) == ("bitplane_stacked", False), key


def test_layer_plan_reads_older_manifest_entry():
    """A manifest entry written by an older build (a cost planner's
    ``est_cost`` and ``"measured"`` source) still loads: the cost is
    dropped, the source kept as written."""
    plan = LayerPlan.from_json({
        "mode": "bitplane_stacked", "group_size": 8, "with_luts": False,
        "k": 64, "n": 32, "source": "measured", "est_cost": 1.0,
        "kv_dtype": None,
    })
    assert plan == LayerPlan(mode="bitplane_stacked", group_size=8,
                             with_luts=False, k=64, n=32, source="measured")
    assert "est_cost" not in plan.to_json()


def test_freeze_model_pinned_mode_matches_legacy():
    w = jnp.asarray(np.random.default_rng(2).normal(size=(32, 16)),
                    jnp.float32)
    art = freeze_model({"w": w}, DAConfig(x_signed=True), mode="da_lut")
    leaf = art.params["w"]
    assert isinstance(leaf, PackedWeights)
    assert leaf.mode == "lut" and leaf.has_luts
    assert art.plan["w"].source == "pinned"


def test_pinned_freeze_drops_dead_luts():
    """pin_modes=True with a storage-free winner writes no PMAs (the LUTs
    would be dead bytes in every artifact); pin_modes=False keeps feasible
    LUTs so runtime dispatch can still read them at other shapes."""
    cfg = DAConfig(x_signed=True)
    w = {"wq": jnp.asarray(np.random.default_rng(7).normal(size=(64, 64)),
                           jnp.float32)}
    # 16 rows: the rule picks stacked bit-planes although the LUTs fit
    pinned = freeze_model(w, cfg, m_hint=16)
    assert pinned.params["wq"].mode == "bitplane_stacked"
    assert not pinned.params["wq"].has_luts
    assert not pinned.plan["wq"].with_luts
    loose = freeze_model(w, cfg, m_hint=16, pin_modes=False)
    assert loose.params["wq"].mode == "auto" and loose.params["wq"].has_luts


def test_skip_context_subtrees_stay_float():
    """A weight-named leaf under a router/conv/table subtree is not a VMM
    and must not be frozen (ancestor names gate, not just the leaf name)."""
    w = jnp.ones((8, 4), jnp.float32)
    art = freeze_model({"router": {"w": w}, "head": {"w": w}},
                       DAConfig(x_signed=True), mode="lut")
    assert not isinstance(art.params["router"]["w"], PackedWeights)
    assert isinstance(art.params["head"]["w"], PackedWeights)
    assert set(art.plan) == {"head/w"}


# ---------------------------------------------------------------------------
# persistence: checkpoint round-trip of PackedWeights
# ---------------------------------------------------------------------------

def _bare_frozen_tree():
    rng = np.random.default_rng(3)
    params = {
        "proj": {"wq": jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)},
        "experts": {"w_up": jnp.asarray(
            rng.normal(size=(3, 16, 8)), jnp.float32)},  # stacked [E, K, N]
        "norm": {"scale": jnp.ones((16,), jnp.float32)},  # stays float
    }
    return freeze_model(params, DAConfig(x_signed=True), mode="lut")


def test_artifact_roundtrip_bit_exact(tmp_path):
    art = _bare_frozen_tree()
    d = str(tmp_path / "art")
    save_artifact(d, art)
    back = load_artifact(d)
    for key in ("proj", "experts"):
        name = next(iter(art.params[key]))
        a, b = art.params[key][name], back.params[key][name]
        assert isinstance(b, PackedWeights)
        np.testing.assert_array_equal(np.asarray(a.wq), np.asarray(b.wq))
        assert b.wq.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(a.w_scale), np.asarray(b.w_scale))
        np.testing.assert_array_equal(np.asarray(a.luts), np.asarray(b.luts))
        assert b.cfg == a.cfg and b.mode == a.mode
    np.testing.assert_array_equal(
        np.asarray(art.params["norm"]["scale"]),
        np.asarray(back.params["norm"]["scale"]))
    assert back.plan == art.plan
    assert back.da_cfg == art.da_cfg


def test_artifact_crc_detects_corruption(tmp_path):
    art = _bare_frozen_tree()
    d = str(tmp_path / "art")
    save_artifact(d, art)
    man_path = os.path.join(d, "manifest.json")
    man = json.load(open(man_path))
    man["arrays"]["proj/wq/wq"]["crc32"] ^= 0xBAD
    json.dump(man, open(man_path, "w"))
    with pytest.raises(IOError, match="checksum"):
        load_artifact(d)


def test_restored_artifact_identical_outputs_jit_and_vmap(tmp_path):
    """The restored codes/scales/LUTs drive da_matmul to the exact same
    floats as the originals — under jit and under expert-stacked vmap."""
    art = _bare_frozen_tree()
    d = str(tmp_path / "art")
    save_artifact(d, art)
    back = load_artifact(d)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 24)), jnp.float32)
    f = jax.jit(lambda p, xs: da_matmul(xs, p))
    np.testing.assert_array_equal(
        np.asarray(f(art.params["proj"]["wq"], x)),
        np.asarray(f(back.params["proj"]["wq"], x)))
    xe = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)  # [E, M, K]
    g = jax.jit(lambda p, xs: jax.vmap(lambda pe, xs_e: pe(xs_e))(p, xs))
    np.testing.assert_array_equal(
        np.asarray(g(art.params["experts"]["w_up"], xe)),
        np.asarray(g(back.params["experts"]["w_up"], xe)))


def test_ckpt_template_restore_keeps_packedweights(tmp_path):
    """The classic template path (elastic restarts) round-trips frozen
    trees too: PackedWeights leaves restore bit-exactly into the template."""
    art = _bare_frozen_tree()
    ckpt.save(str(tmp_path), 7, art.params)
    out = ckpt.restore(str(tmp_path), 7, art.params)
    leaf = out["proj"]["wq"]
    assert isinstance(leaf, PackedWeights) and leaf.mode == "lut"
    np.testing.assert_array_equal(
        np.asarray(leaf.luts), np.asarray(art.params["proj"]["wq"].luts))


def test_load_artifact_rejects_non_artifact(tmp_path):
    ckpt.save_tree(str(tmp_path / "plain"), {"a": jnp.zeros((2,))})
    with pytest.raises(IOError, match="not a DA artifact"):
        load_artifact(str(tmp_path / "plain"))


def test_load_artifact_demotes_stale_backend_modes(tmp_path):
    """An artifact planned against a backend this build doesn't register
    degrades to mode='auto' with a warning — never KeyError at dispatch."""
    art = _bare_frozen_tree()
    d = str(tmp_path / "art")
    save_artifact(d, art)
    man_path = os.path.join(d, "manifest.json")
    man = json.load(open(man_path))
    for meta in man["packed"].values():
        meta["mode"] = "warp_drive"
    for plan in man["plan"].values():
        plan["mode"] = "warp_drive"
    json.dump(man, open(man_path, "w"))
    with pytest.warns(UserWarning, match="not registered"):
        back = load_artifact(d)
    assert back.params["proj"]["wq"].mode == "auto"
    assert back.plan["proj/wq"].mode == "auto"
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 24)), jnp.float32)
    assert np.asarray(da_matmul(x, back.params["proj"]["wq"])).shape == (2, 16)


# ---------------------------------------------------------------------------
# per-layer memory report
# ---------------------------------------------------------------------------

def test_memory_report_per_layer_plan_rows():
    art = _bare_frozen_tree()
    rep = da_memory_report(art.params)
    assert rep["da_matrices"] == 2 and len(rep["layers"]) == 2
    by_name = {r["layer"]: r for r in rep["layers"]}
    row = by_name["proj/wq"]
    assert row["mode"] == "lut" and row["group_size"] == 8
    assert row["code_bytes"] == 24 * 16          # int8 codes
    assert row["lut_bytes"] == 3 * 256 * 16 * 4  # [G=3, 2^8, N=16] int32
    assert row["cell_blowup"] == pytest.approx(32.0)
    # aggregate keys unchanged (legacy surface)
    assert rep["weight_cells"] == 24 * 16 + 3 * 16 * 8
    assert rep["cell_blowup"] > 0


# ---------------------------------------------------------------------------
# end-to-end: freeze once, serve many
# ---------------------------------------------------------------------------

def test_serve_from_artifact_matches_in_memory(tmp_path):
    """The acceptance path: freeze a smoke model to a DAArtifact on disk,
    reload it template-free (zero float weights in scope), serve greedy
    decode through ServeEngine, and match the in-memory frozen model's
    tokens.  The plan must be per-layer: at least two layer shapes get
    different backends (the lm head's LUTs bust the budget)."""
    from repro.models.model import init_model
    from repro.serve.engine import Request, ServeEngine

    cfg = _serve_cfg(vocab=NO_LUT_VOCAB)
    params = init_model(KEY, cfg)
    eng_mem = ServeEngine(cfg, params, batch_size=2, max_len=32,
                          da_mode="auto")
    del params  # floats out of scope — everything below is packed

    # planner actually differed across layer shapes
    plans = eng_mem.artifact.plan
    assert len({(p.mode, p.with_luts) for p in plans.values()}) >= 2
    modes = {p.mode for p in plans.values()}
    assert {"lut", "bitplane_stacked"} <= modes

    d = str(tmp_path / "artifact")
    eng_mem.save_artifact(d)

    prompts = {uid: np.random.default_rng(10 + uid).integers(
        0, cfg.vocab, 5 + uid) for uid in range(3)}

    def serve(eng):
        for uid, pr in prompts.items():
            eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=6))
        done = eng.run()
        return {uid: r.generated for uid, r in done.items()}

    got_mem = serve(eng_mem)

    # cold boot: fresh engine from disk only — no float params anywhere
    eng_disk = ServeEngine.from_artifact(d, batch_size=2, max_len=32)
    assert eng_disk.cfg.vocab == cfg.vocab
    rep = da_memory_report(eng_disk.params)
    assert rep["da_matrices"] == len(plans)
    got_disk = serve(eng_disk)

    assert got_mem.keys() == got_disk.keys()
    for uid in got_mem:
        assert got_mem[uid] == got_disk[uid], uid


def test_artifact_plan_survives_roundtrip_with_model_cfg(tmp_path):
    from repro.models.model import init_model

    cfg = _serve_cfg(n_layers=2)
    art = freeze_model(init_model(KEY, cfg), DAConfig(x_signed=True),
                       m_hint=2, model_cfg=cfg)
    d = str(tmp_path / "a")
    save_artifact(d, art)
    back = load_artifact(d)
    assert back.model_cfg == cfg
    assert back.plan == art.plan
    assert isinstance(back, DAArtifact)
    assert all(isinstance(p, LayerPlan) for p in back.plan.values())

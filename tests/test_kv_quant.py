"""Quantized paged KV cache: numerics, backend bit-parity, pool-op
transparency (scales ride inside the page), artifact plumbing, metrics.

The invariants under test:

* fused Pallas kernel == XLA gather read, bit-for-bit, on int8 AND int4
  pools (the same parity the fp tests assert — dequant is one shared
  elementwise formula, applied in-register by the kernel);
* every pool operation (copy_page COW, defrag remap, spec rollback,
  prefix-trie sharing) moves/shares the in-page scales together with the
  codes — no dequant round-trips, no scale drift;
* the fp16 escape hatch is byte-for-byte today's cache layout;
* artifacts record the KV precision and ``from_artifact`` refuses to
  silently flatten a per-layer plan.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.core.da import DAConfig
from repro.core.freeze import da_memory_report, freeze_model, load_artifact, \
    save_artifact
from repro.kernels.paged_attention import paged_attention
from repro.models import kv_quant as kvq
from repro.models.attention import PagedKVCache
from repro.models.model import init_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import (
    PagePool,
    copy_page,
    defrag,
    init_paged_caches,
    kv_page_bytes,
    kv_token_bytes,
    resolve_kv_dtypes,
)
from repro.spec import SpecConfig

KEY = jax.random.key(0)
MAX_NEW = 4


def _smoke_cfg(**kw):
    return dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                               moe_dropless=True, **kw)


# ---------------------------------------------------------------------------
# numerics: pack/unpack exactness, quantization error bound
# ---------------------------------------------------------------------------


def test_int4_pack_unpack_roundtrip_exact():
    """Every nibble value in [-7, 7], both lanes: pack∘unpack is identity
    (integers are exact — backend bit-parity rests on this)."""
    lo, hi = np.meshgrid(np.arange(-7, 8), np.arange(-7, 8))
    codes = jnp.asarray(np.stack([lo.ravel(), hi.ravel()], -1), jnp.int8)
    packed = kvq.pack_int4(codes)
    assert packed.shape == codes.shape[:-1] + (1,)
    np.testing.assert_array_equal(np.asarray(kvq.unpack_int4(packed)),
                                  np.asarray(codes))


@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_quantize_error_bounded_by_half_scale(fmt, rng):
    x = jnp.asarray(rng.normal(size=(5, 3, 2, 8)) * 10, jnp.float32)
    codes, scale = kvq.quantize_kv(x, fmt)
    assert codes.dtype == jnp.int8 and scale.dtype == kvq.KV_SCALE_DTYPE
    assert scale.shape == x.shape[:-1] + (1,)
    deq = kvq.dequantize_kv(codes, scale, fmt, jnp.float32)
    # symmetric rounding: |deq - x| <= scale/2 elementwise (plus fp16
    # rounding of the scale itself, covered by the 1.01 slack)
    bound = np.asarray(scale.astype(jnp.float32)) * 0.505
    assert np.all(np.abs(np.asarray(deq) - np.asarray(x)) <= bound)


def test_quantize_all_zero_rows_are_exact():
    x = jnp.zeros((2, 4, 2, 8), jnp.float32)
    for fmt in ("int8", "int4"):
        codes, scale = kvq.quantize_kv(x, fmt)
        assert not np.any(np.asarray(codes)) and not np.any(np.asarray(scale))
        np.testing.assert_array_equal(
            np.asarray(kvq.dequantize_kv(codes, scale, fmt, jnp.float32)),
            np.asarray(x))


def test_kv_format_inference_and_mismatch():
    k8 = jnp.zeros((4, 2, 2, 16), jnp.int8)
    k4 = jnp.zeros((4, 2, 2, 8), jnp.int8)
    s = jnp.zeros((4, 2, 2, 1), jnp.float16)
    assert kvq.kv_format(k8, None, 16) == "fp"
    assert kvq.kv_format(k8, s, 16) == "int8"
    assert kvq.kv_format(k4, s, 16) == "int4"
    with pytest.raises(ValueError, match="neither int8"):
        kvq.kv_format(jnp.zeros((4, 2, 2, 5), jnp.int8), s, 16)


# ---------------------------------------------------------------------------
# backend parity on quantized pools (the PR-6 guarantee, to a few ulps)
# ---------------------------------------------------------------------------


def _quantized_paged_case(rng, fmt, t, lens, ps=8, n_pages=12):
    from test_paged_attention import _random_paged_case

    q, ck, cv, tbl, tpos = _random_paged_case(rng, jnp.float32, t, lens,
                                              ps=ps, n_pages=n_pages)
    kc, ks = kvq.quantize_kv(ck, fmt)
    vc, vs = kvq.quantize_kv(cv, fmt)
    return q, kc, ks, vc, vs, tbl, tpos


@pytest.mark.parametrize("fmt", ["int8", "int4"])
@pytest.mark.parametrize("t", [1, 4])
def test_fused_bitwise_equals_gather_quantized(fmt, t):
    """Fused kernel == gather read on quantized pools within 2 ulps of the
    output dtype (``assert_within_ulps``), over the fp parity case's rows
    (``PARITY_LENS``): the scale slivers ride the same page copies and
    dequantization uses the gather path's exact elementwise formula; the
    online softmax's block order keeps the reads from bitwise equality, so
    the gather read sums in that order too (``blocked_gather_read``)."""
    from test_paged_attention import (PARITY_LENS, PS, assert_within_ulps,
                                      blocked_gather_read)

    rng = np.random.default_rng(0)
    q, kc, ks, vc, vs, tbl, tpos = _quantized_paged_case(
        rng, fmt, t, lens=PARITY_LENS, ps=PS)
    kw = dict(k_scale=ks, v_scale=vs)
    assert_within_ulps(blocked_gather_read(q, kc, vc, tbl, tpos, **kw),
                       paged_attention(q, kc, vc, tbl, tpos, **kw))


def test_fused_quantized_ignores_unreferenced_pages():
    """NaN-poisoning the SCALES (not just the codes) of pages no table names
    must not change the fused output — the walk copies neither."""
    rng = np.random.default_rng(2)
    q, kc, ks, vc, vs, tbl, tpos = _quantized_paged_case(
        rng, "int8", 1, lens=[9, 17], ps=8)
    named = set(np.asarray(tbl).ravel().tolist())
    unwalked = jnp.asarray(
        [p for p in range(kc.shape[0]) if p not in named])
    out = paged_attention(q, kc, vc, tbl, tpos, k_scale=ks, v_scale=vs)
    poisoned = paged_attention(
        q, kc.at[unwalked].set(127), vc.at[unwalked].set(-127),
        tbl, tpos,
        k_scale=ks.at[unwalked].set(jnp.nan),
        v_scale=vs.at[unwalked].set(jnp.nan))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(poisoned))


# ---------------------------------------------------------------------------
# cache layout: zeros(), validation, fp16 escape hatch
# ---------------------------------------------------------------------------


def test_zeros_fp16_escape_hatch_is_todays_layout():
    cfg = _smoke_cfg()
    plain = PagedKVCache.zeros(cfg, 6, 4, jnp.float32)
    hatch = PagedKVCache.zeros(cfg, 6, 4, jnp.float32, kv_dtype="fp16")
    assert hatch.k_scale is None and hatch.v_scale is None
    assert hatch.k.shape == plain.k.shape and hatch.k.dtype == plain.k.dtype
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 plain, hatch)


@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_zeros_quantized_layout(fmt):
    cfg = _smoke_cfg()
    hd = cfg.head_dim_
    c = PagedKVCache.zeros(cfg, 6, 4, jnp.float32, kv_dtype=fmt)
    hd_p = hd // 2 if fmt == "int4" else hd
    assert c.k.shape == (6, 4, cfg.n_kv_heads, hd_p)
    assert c.k.dtype == jnp.int8
    assert c.k_scale.shape == (6, 4, cfg.n_kv_heads, 1)
    assert c.k_scale.dtype == kvq.KV_SCALE_DTYPE


def test_init_paged_caches_validation_is_loud():
    cfg = _smoke_cfg()
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        init_paged_caches(cfg, 6, 4, jnp.float32, kv_dtypes="int2")
    odd = dataclasses.replace(cfg, head_dim=cfg.head_dim_ + 1)
    with pytest.raises(ValueError, match="even head_dim"):
        init_paged_caches(odd, 6, 4, jnp.float32, kv_dtypes="int4")
    with pytest.raises(ValueError, match="outside this model's period"):
        resolve_kv_dtypes(cfg, {"pos_99": "int8"})
    # per-pos dict: named positions override, the rest follow cfg.kv_dtype
    mixed = resolve_kv_dtypes(dataclasses.replace(cfg, kv_dtype="int8"),
                              {"pos_0": "fp16"})
    assert mixed["pos_0"] == "fp16"
    assert all(v == "int8" for k, v in mixed.items() if k != "pos_0")


def test_byte_accounting_matches_device_arrays():
    cfg = _smoke_cfg()
    hd, kv = cfg.head_dim_, cfg.n_kv_heads
    # fp: 2 tensors * kv * hd * itemsize; int8: codes + 2B scale per head
    assert kv_token_bytes(cfg, "fp16", dtype=jnp.float32) == 2 * kv * hd * 4
    assert kv_token_bytes(cfg, "int8") == 2 * kv * (hd + 2)
    assert kv_token_bytes(cfg, "int4") == 2 * kv * (hd // 2 + 2)
    caches = init_paged_caches(cfg, 6, 4, jnp.float32, kv_dtypes="int8")
    got = sum(leaf.size * leaf.dtype.itemsize
              for leaf in jax.tree.leaves(caches))
    assert got == 6 * kv_page_bytes(cfg, 4, "int8")


def test_pool_stats_price_pages_in_bytes():
    pool = PagePool(8, page_bytes=1000)
    pool.alloc(3)
    s = pool.stats()
    assert s["page_bytes"] == 1000
    assert s["pool_bytes"] == 8000
    assert s["used_bytes"] == 3000
    assert s["free_bytes"] == 4000  # page 0 is reserved, not free


# ---------------------------------------------------------------------------
# pool-op transparency: scales move/share with values, no dequant round-trip
# ---------------------------------------------------------------------------


def _written_quant_pool(cfg, n_pages, ps, pages):
    """Quantized pool with recognizable rows on ``pages`` (codes AND scales
    vary per page), junk elsewhere."""
    rng = np.random.default_rng(3)
    caches = init_paged_caches(cfg, n_pages, ps, jnp.float32,
                               kv_dtypes="int8")
    c = caches["pos_0"]
    rows = jnp.asarray(
        rng.normal(size=(len(pages), ps, cfg.n_kv_heads, cfg.head_dim_))
        * np.arange(1, len(pages) + 1)[:, None, None, None], jnp.float32)
    codes, scale = kvq.quantize_kv(rows, "int8")
    idx = jnp.asarray(pages)
    c = PagedKVCache(
        k=c.k.at[:, idx].set(codes), v=c.v.at[:, idx].set(-codes),
        k_scale=c.k_scale.at[:, idx].set(scale),
        v_scale=c.v_scale.at[:, idx].set(scale * 2))
    return {"pos_0": c}


def test_copy_page_moves_scales_with_codes():
    cfg = _smoke_cfg()
    caches = _written_quant_pool(cfg, 8, 4, pages=[3])
    out = copy_page(caches, src=3, dst=5)["pos_0"]
    for leaf_src, leaf_dst in ((out.k[:, 3], out.k[:, 5]),
                               (out.k_scale[:, 3], out.k_scale[:, 5]),
                               (out.v_scale[:, 3], out.v_scale[:, 5])):
        np.testing.assert_array_equal(np.asarray(leaf_src),
                                      np.asarray(leaf_dst))


def test_defrag_remaps_scales_with_codes_and_poisons_nothing_live():
    """Defrag on a quantized pool: dequantized content of every live page is
    bit-identical after compaction (codes and scales moved together), even
    with vacated source pages NaN/junk-poisoned afterwards."""
    cfg = _smoke_cfg()
    n_pages, ps = 9, 4
    pool = PagePool(n_pages)
    allocated = pool.alloc(8)
    tables = [[5, 2], [7]]
    pool.free([p for p in allocated if p not in {5, 2, 7}])
    caches = _written_quant_pool(cfg, n_pages, ps, pages=[5, 2, 7])

    def dequant_rows(caches, tables):
        c = caches["pos_0"]
        out = []
        for t in tables:
            idx = jnp.asarray(t)
            out.append(np.asarray(kvq.dequantize_kv(
                c.k[:, idx], c.k_scale[:, idx], "int8", jnp.float32)))
        return out

    before = dequant_rows(caches, tables)
    caches = defrag(caches, pool, tables)
    assert sorted(p for t in tables for p in t) == [1, 2, 3]
    # poison everything defrag vacated: live content must not reference it
    c = caches["pos_0"]
    vacated = jnp.asarray([p for p in range(4, n_pages)])
    caches = {"pos_0": PagedKVCache(
        k=c.k.at[:, vacated].set(127), v=c.v.at[:, vacated].set(127),
        k_scale=c.k_scale.at[:, vacated].set(jnp.nan),
        v_scale=c.v_scale.at[:, vacated].set(jnp.nan))}
    for b, a in zip(before, dequant_rows(caches, tables)):
        np.testing.assert_array_equal(b, a)


# ---------------------------------------------------------------------------
# serving end-to-end: token identity across cache-sharing features at int8
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    cfg = _smoke_cfg()
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, 8)
    prompts = {uid: np.concatenate([shared,
                                    rng.integers(0, cfg.vocab, 2 + uid)])
               for uid in range(4)}
    return cfg, params, prompts


def _serve(cfg, params, prompts, **kw):
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=4,
                      **kw)
    for uid, pr in prompts.items():
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
    done = eng.run()
    return {u: r.generated for u, r in done.items()}, eng.metrics()


def test_prefix_cache_token_identity_on_quantized_pages(served):
    """Trie sharing + COW forks on int8 pages: caching on == caching off
    (deterministic write-once quantization — a shared page's codes and
    scales are exactly what un-shared prefill would have written)."""
    cfg, params, prompts = served
    base, mb = _serve(cfg, params, prompts, kv_dtype="int8")
    out, m = _serve(cfg, params, prompts, kv_dtype="int8", prefix_cache=True)
    assert out == base
    assert m["prefix_cache"]["hits"] > 0  # sharing actually happened
    assert mb["kv"]["kv_dtypes"]["pos_0"] == "int8"


def test_spec_rollback_token_identity_on_quantized_pages(served):
    """Speculative decoding over int8 pages: rejected draft rows roll back
    by page bookkeeping alone (write-once scales leave no numeric trace) —
    greedy output is exactly the non-speculative stream, zero pages leak."""
    cfg, params, prompts = served
    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=6,
                      disable_below=0.0)
    base, _ = _serve(cfg, art.params, prompts, kv_dtype="int8")
    out, m = _serve(cfg, art.params, prompts, kv_dtype="int8", spec=spec)
    assert out == base
    assert m["spec"]["rounds"] > 0
    assert m["pool"]["used_pages"] == 0


def test_metrics_kv_block(served):
    cfg, params, prompts = served
    _, m = _serve(cfg, params, prompts, kv_dtype="int4")
    kv = m["kv"]
    assert set(kv["kv_dtypes"].values()) == {"int4"}
    assert kv["bytes_per_token"] == cfg.n_periods * kv_token_bytes(cfg,
                                                                   "int4")
    assert kv["capacity_multiplier"] > 1.8
    assert m["pool"]["pool_bytes"] == \
        m["pool"]["n_pages"] * m["pool"]["page_bytes"]
    # fp16 engines report the multiplier as exactly 1
    _, m0 = _serve(cfg, params, prompts)
    assert m0["kv"]["capacity_multiplier"] == 1.0


# ---------------------------------------------------------------------------
# artifact plumbing: plans record KV precision, loaders can't mismatch it
# ---------------------------------------------------------------------------


def test_artifact_records_and_restores_kv_dtype(served, tmp_path):
    cfg, params, prompts = served
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=4,
                      da_mode="bitplane_stacked", kv_dtype="int8")
    path = str(tmp_path / "art_int8")
    eng.save_artifact(path)
    art = load_artifact(path)
    assert art.model_cfg.kv_dtype == "int8"
    wk_plans = {k: p for k, p in art.plan.items() if k.endswith("/wk")}
    assert wk_plans and all(p.kv_dtype == "int8" for p in wk_plans.values())
    # non-KV leaves carry no kv dtype
    assert all(p.kv_dtype is None for k, p in art.plan.items()
               if k.endswith("/wq"))
    booted = ServeEngine.from_artifact(path, batch_size=2, max_len=32,
                                       page_size=4)
    assert booted._rt.kv_dtypes["pos_0"] == "int8"


def test_from_artifact_refuses_to_flatten_heterogeneous_plan(tmp_path):
    # a 2-position period (both attention mixers) via MoE cadence, so the
    # plan can carry two different KV dtypes
    cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen2-moe-a2.7b"]),
                              moe_dropless=True, moe_period=2, d_ff=64)
    assert cfg.period == 2 and cfg.n_layers % 2 == 0
    params = init_model(KEY, cfg)
    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg,
                       kv_dtype_overrides={"pos_1": "int8"})
    path = str(tmp_path / "art_mixed")
    save_artifact(path, art)
    with pytest.raises(ValueError, match="silently flatten"):
        ServeEngine.from_artifact(path, batch_size=2, max_len=32,
                                  page_size=4, kv_dtype="int8")
    # without the override the per-layer plan boots as frozen
    eng = ServeEngine.from_artifact(path, batch_size=2, max_len=32,
                                    page_size=4)
    assert eng._rt.kv_dtypes == {"pos_0": "fp16", "pos_1": "int8"}


def test_da_memory_report_prices_kv_beside_weights(served):
    cfg, params, prompts = served
    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    rep = da_memory_report(art.params,
                           model_cfg=dataclasses.replace(cfg,
                                                         kv_dtype="int8"))
    kv = rep["kv"]
    assert kv["kv_dtypes"]["pos_0"] == "int8"
    assert kv["bytes_per_token"] == cfg.n_periods * kv_token_bytes(cfg,
                                                                   "int8")
    assert kv["capacity_multiplier"] > 1.0

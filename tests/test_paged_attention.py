"""Fused Pallas paged-attention kernel vs the XLA gather read, plus the
decode-path correctness fixes that rode along with it.

The load-bearing properties: the fused in-kernel page walk is bit-identical
to the gather read at the default float32 softmax (unit level across
page-boundary-straddling lengths and ragged mixed prefill+decode batches,
and end-to-end through the serving runtime — greedy, speculative, prefix
cache on/off with COW'd shared pages); the fused lowering contains no
full-page-table KV gather; the fused QKV projection equals three separate
engine calls exactly; and the three bugfixes (platform-derived interpret
default, fp32-exact bk auto-shrink, warm-dense-cache chunked prefill)
behave as documented."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.core.engine import (
    da_matmul,
    da_qkv_matmul,
    get_attn_backend,
    registered_attn_backends,
    select_attn_backend,
)
from repro.kernels.paged_attention import paged_attention
from repro.models.attention import paged_gather_read
from repro.models.model import init_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import pages_for, table_width

KEY = jax.random.key(0)
MAX_NEW = 4


def _smoke_cfg(**kw):
    return dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                               moe_dropless=True, **kw)


# ---------------------------------------------------------------------------
# unit level: fused kernel vs gather read over the same pool
# ---------------------------------------------------------------------------


def _random_paged_case(rng, dtype, t, lens, ps=8, n_pages=12):
    """Pool + permuted page tables + ragged tpos for len(lens) rows.

    Pages are allocated out of order (physical != logical) and unused table
    slots point at the garbage page 0, exactly like the serving allocator.
    ``tpos`` covers the last ``t`` positions of each row — T=1 is decode,
    T>1 a coalesced mixed step whose leading columns act as pad lanes for
    short rows (clamped to 0, masked by the causal comparison).
    """
    b, kv, hd = len(lens), 2, 16
    h = 4
    w = max(pages_for(max(lens), ps) + 1, 3)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), dtype)
    ck = jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)), dtype)
    cv = jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)), dtype)
    perm = rng.permutation(np.arange(1, n_pages))
    tbl = np.zeros((b, w), np.int32)
    tpos = np.zeros((b, t), np.int32)
    pi = 0
    for i, ln in enumerate(lens):
        npg = pages_for(ln, ps)
        tbl[i, :npg] = perm[pi:pi + npg]
        pi += npg
        tpos[i] = np.maximum(np.arange(ln - t, ln), 0)
    return q, ck, cv, jnp.asarray(tbl), jnp.asarray(tpos)


@pytest.mark.parametrize("mask_mode", ["where", "additive"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bitwise_equals_gather(dtype, t, mask_mode):
    """Page-boundary-straddling lengths (ps−1, ps, 2·ps+3), permuted
    physical pages, garbage column present: fused == gather bit-for-bit at
    float32 softmax for decode and ragged mixed steps."""
    rng = np.random.default_rng(0)
    ps = 8
    q, ck, cv, tbl, tpos = _random_paged_case(
        rng, dtype, t, lens=[ps - 1, ps, 2 * ps + 3], ps=ps)
    kw = dict(softmax_dtype="float32", mask_mode=mask_mode)
    ref = paged_gather_read(q, ck, cv, tbl, tpos, **kw)
    out = paged_attention(q, ck, cv, tbl, tpos, **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_fused_bf16_softmax_close_not_bitwise():
    """Sub-f32 softmax dtypes: XLA fuses exp+reduce keeping f32 across the
    pair, which an op-by-op kernel cannot reproduce — documented as
    within-rounding, asserted here as allclose at bf16 tolerance."""
    rng = np.random.default_rng(1)
    q, ck, cv, tbl, tpos = _random_paged_case(
        rng, jnp.float32, 1, lens=[13, 7], ps=8)
    kw = dict(softmax_dtype="bfloat16", mask_mode="where")
    ref = paged_gather_read(q, ck, cv, tbl, tpos, **kw)
    out = paged_attention(q, ck, cv, tbl, tpos, **kw)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=0, atol=2e-2)


def test_fused_ignores_unreferenced_and_garbage_pages():
    """NaN-poisoning pages no table names must not change the fused output
    (the walk never touches them), and rewriting the garbage page 0 with
    finite junk must not either (pad slots walk it, but its rows carry
    exactly zero softmax weight — same contract as the gather path)."""
    rng = np.random.default_rng(2)
    q, ck, cv, tbl, tpos = _random_paged_case(
        rng, jnp.float32, 1, lens=[9, 17], ps=8)
    named = set(np.asarray(tbl).ravel().tolist())
    unwalked = [p for p in range(ck.shape[0]) if p not in named]
    assert unwalked, "case must leave some pages unreferenced"
    out = paged_attention(q, ck, cv, tbl, tpos)
    ckp = ck.at[jnp.asarray(unwalked)].set(jnp.nan)
    cvp = cv.at[jnp.asarray(unwalked)].set(jnp.nan)
    ckp = ckp.at[0].set(7.5)
    cvp = cvp.at[0].set(-3.25)
    poisoned = paged_attention(q, ckp, cvp, tbl, tpos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(poisoned))


# ---------------------------------------------------------------------------
# engine registry + dispatch
# ---------------------------------------------------------------------------


def test_attn_registry_and_dispatch():
    names = set(registered_attn_backends())
    assert {"gather", "fused"} <= names
    with pytest.raises(ValueError, match="unknown paged-attention"):
        get_attn_backend("nope")
    # off-TPU: auto resolves to the gather read; explicit names pass through
    assert select_attn_backend("auto") == "gather"
    assert select_attn_backend(None) == "gather"
    assert select_attn_backend("fused") == "fused"
    with pytest.raises(ValueError, match="unknown paged-attention"):
        select_attn_backend("nope")


# ---------------------------------------------------------------------------
# end-to-end: serving runtime token identity, gather vs fused
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """Frozen smoke artifact (the fused QKV pass runs end-to-end) + prompts
    sharing a 2-page prefix (so the prefix cache has something to share and
    COW) + per-config decode helper."""
    from repro.core.da import DAConfig
    from repro.core.freeze import freeze_model

    cfg = _smoke_cfg()
    art = freeze_model(init_model(KEY, cfg), DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, 16)  # 2 pages at page_size=8
    prompts = {
        uid: np.concatenate([shared, rng.integers(0, cfg.vocab, 2 + uid)])
        for uid in range(6)
    }

    def run(**kw):
        eng = ServeEngine(cfg, art.params, batch_size=2, max_len=48,
                          page_size=8, **kw)
        for uid, pr in prompts.items():
            eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
        done = eng.run()
        return {uid: list(done[uid].generated) for uid in prompts}

    return run


def test_serve_tokens_identical_greedy(served):
    """Plain continuous batching (chunked prefill + decode through 2 lanes):
    fused backend decodes the very tokens the gather backend does."""
    assert served(paged_attn="gather") == served(paged_attn="fused")


def test_serve_tokens_identical_prefix_cache(served):
    """COW'd shared-prefix pages under the fused read: token-identical to
    the gather read, cache on and off."""
    ref = served(paged_attn="gather")
    assert served(paged_attn="fused", prefix_cache=True) == ref
    assert served(paged_attn="gather", prefix_cache=True) == ref


def test_serve_tokens_identical_speculative(served):
    """Spec staging (draft rollouts + batched T=γ+1 verify) runs the fused
    read in every stage; greedy output stays token-identical."""
    from repro.spec import SpecConfig

    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=6,
                      disable_below=0.0)
    ref = served(paged_attn="gather")
    assert served(paged_attn="fused", spec=spec) == ref


# ---------------------------------------------------------------------------
# lowering: the full-page-table KV gather is gone from the fused path
# ---------------------------------------------------------------------------


def test_fused_lowering_has_no_page_table_gather():
    from repro.launch.hlo_tools import ops_of_kind
    from repro.serve.kvcache import init_paged_caches
    from repro.serve.scheduler import make_paged_step

    cfg = _smoke_cfg()
    params = init_model(KEY, cfg)
    b, ps, max_len = 2, 8, 32
    w = table_width(max_len, ps)
    n_pages = 1 + b * pages_for(max_len, ps)
    caches = init_paged_caches(cfg, n_pages, ps, cfg.dtype())
    args = (
        params, caches,
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, w), jnp.int32), jnp.zeros((b,), jnp.int32),
    )
    # the re-materialized KV view is [B, W, ps, kv, hd] per gather
    view_bytes = (b * w * ps * cfg.n_kv_heads * cfg.head_dim_
                  * jnp.dtype(cfg.dtype()).itemsize)

    def biggest_gather(paged_attn):
        step = make_paged_step(dataclasses.replace(cfg, paged_attn=paged_attn))
        hlo = jax.jit(step).lower(*args).compile().as_text()
        gathers = ops_of_kind(hlo, "gather")
        return max((bts for _, bts in gathers), default=0)

    assert biggest_gather("gather") >= view_bytes  # the op we are removing
    assert biggest_gather("fused") < view_bytes    # gone from the fused path


# ---------------------------------------------------------------------------
# fused QKV projection
# ---------------------------------------------------------------------------


def test_fused_qkv_bit_identical_to_separate_calls():
    from repro.core.da import DAConfig
    from repro.core.engine import pack_weights

    rng = np.random.default_rng(3)
    dacfg = DAConfig(x_signed=True)
    d, qd, kvd = 64, 64, 32
    packs = tuple(
        pack_weights(jnp.asarray(rng.standard_normal((d, n)), jnp.float32),
                     dacfg)
        for n in (qd, kvd, kvd)
    )
    x = jnp.asarray(rng.standard_normal((2, 3, d)), jnp.float32)
    fused = da_qkv_matmul(x, packs)
    for got, p in zip(fused, packs):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(da_matmul(x, p)))


def test_fused_qkv_draft_precision_matches():
    """The truncated-bitplane draft pass fuses too: under x_bits_override
    the shared codes are truncated exactly as da_matmul truncates them."""
    from repro.core.da import DAConfig
    from repro.core.engine import pack_weights, x_bits_override

    rng = np.random.default_rng(4)
    dacfg = DAConfig(x_signed=True)
    packs = tuple(
        pack_weights(jnp.asarray(rng.standard_normal((48, n)), jnp.float32),
                     dacfg)
        for n in (32, 16, 16)
    )
    x = jnp.asarray(rng.standard_normal((4, 48)), jnp.float32)
    with x_bits_override(4):
        fused = da_qkv_matmul(x, packs)
        seps = [da_matmul(x, p) for p in packs]
    for got, ref in zip(fused, seps):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# bugfixes
# ---------------------------------------------------------------------------


def test_pallas_interpret_default_derives_from_platform():
    """Off-TPU the kernels must default to interpreter execution (the old
    interpret=True default silently interpreted ON TPU as well)."""
    from repro.core.da import DAConfig
    from repro.kernels import bitplane_vmm, paged_attention as pa
    from repro.kernels.ref import bitplane_vmm_ref

    assert bitplane_vmm._default_interpret() is (
        jax.default_backend() != "tpu")
    assert pa._default_interpret() is (jax.default_backend() != "tpu")
    rng = np.random.default_rng(5)
    cfg = DAConfig(x_signed=True)
    xq = jnp.asarray(rng.integers(-128, 128, (4, 32)), jnp.int32)
    wq = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
    # no interpret= argument: platform default must pick a runnable mode
    out = bitplane_vmm.bitplane_vmm_pallas(xq, wq, cfg)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(bitplane_vmm_ref(xq, wq.astype(jnp.int32), cfg)))


def test_bitplane_bk_autoshrinks_for_wide_codes():
    """The fp32-exactness bound follows the actual weight-code magnitude:
    wide codes shrink bk instead of silently summing past 2^24."""
    from repro.core.da import DAConfig
    from repro.kernels.bitplane_vmm import (
        _fit_bk,
        _weight_code_bound,
        bitplane_vmm_pallas,
    )

    assert _fit_bk(2048, 127) == 2048          # int8 codes: unchanged
    assert _fit_bk(2048, 1 << 16) == 128       # 16-bit codes: shrunk
    with pytest.raises(ValueError, match="exact-integer range"):
        _fit_bk(512, 1 << 24)

    rng = np.random.default_rng(6)
    cfg = DAConfig(x_signed=True)
    xq = jnp.asarray(rng.integers(-128, 128, (4, 256)), jnp.int32)
    wq = jnp.asarray(rng.integers(-4000, 4000, (256, 16)), jnp.int32)
    # int32 storage, concrete codes: bound inspected from the values
    assert _weight_code_bound(wq, None) == int(jnp.max(jnp.abs(wq)))
    out = bitplane_vmm_pallas(xq, wq, cfg, w_maxabs=1 << 16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xq @ wq))
    # traced wide codes must demand an explicit bound, not guess
    with pytest.raises(ValueError, match="w_maxabs"):
        jax.jit(lambda a, b: bitplane_vmm_pallas(a, b, cfg))(xq, wq)


def test_dense_chunked_prefill_warm_cache_raises():
    """A second prefill chunk against a warm dense KVCache cannot see the
    first chunk — the branch must refuse loudly instead of attending over
    the fresh segment only."""
    from repro.models.attention import KVCache, attention_forward, \
        init_attention

    cfg = _smoke_cfg()
    p = init_attention(jax.random.key(1), cfg)
    b, s, t = 1, 32, 4
    cache = KVCache(
        k=jnp.zeros((b, s, cfg.n_kv_heads, cfg.head_dim_)),
        v=jnp.zeros((b, s, cfg.n_kv_heads, cfg.head_dim_)),
        length=jnp.asarray(8, jnp.int32),  # warm: 8 tokens already written
    )
    x = jax.random.normal(jax.random.key(2), (b, t, cfg.d_model))
    pos = jnp.asarray([[8, 9, 10, 11]], jnp.int32)
    with pytest.raises(ValueError, match="warm dense KVCache"):
        attention_forward(p, x, cfg, pos, cache=cache, update_cache=True)
    # a fresh cache (length 0) still prefills fine
    fresh = cache._replace(length=jnp.asarray(0, jnp.int32))
    y, new_cache = attention_forward(
        p, x, cfg, jnp.asarray([[0, 1, 2, 3]], jnp.int32),
        cache=fresh, update_cache=True)
    assert y.shape == (b, t, cfg.d_model)
    assert int(new_cache.length) == t

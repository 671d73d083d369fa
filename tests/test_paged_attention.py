"""Fused Pallas paged-attention kernel vs the XLA gather read, plus the
decode-path correctness fixes that rode along with it.

The load-bearing properties: the fused read, an online softmax over each
row's live key blocks, equals the gather read summed in the same block
order within 2 ulps of the output dtype at the largest output (unit level
across page- and block-boundary lengths, rows longer than two key blocks
and ragged mixed prefill+decode batches) and decodes the very tokens the
gather read does end-to-end through the serving runtime (greedy,
speculative, prefix cache on/off with COW'd shared pages); its walk stops
at each row's last live block (a wider table, or NaN pages past that
block, leave the output bitwise unchanged) and a pad row writes zeros; the
scheduler counts the pages the rows name against the table's slots
(``sched_attn_pages``); the fused lowering contains no full-page-table KV
gather; the fused QKV projection equals three separate engine calls
exactly; and the three bugfixes (platform-derived interpret default,
fp32-exact bk auto-shrink, warm-dense-cache chunked prefill) behave as
documented."""
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
from repro.kernels.paged_attention import (
    NEG_INF,
    pages_per_block,
    paged_attention,
)
from repro.models import kv_quant as kvq
from repro.models.attention import (
    group_queries,
    paged_gather_read,
    ungroup_queries,
)
from repro.models.model import init_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import GARBAGE_PAGE, pages_for, table_width

KEY = jax.random.key(0)
MAX_NEW = 4


def _smoke_cfg(**kw):
    return dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                               moe_dropless=True, **kw)


# ---------------------------------------------------------------------------
# unit level: fused kernel vs gather read over the same pool
# ---------------------------------------------------------------------------


PS = 8
#: keys the fused read folds in per block at PS
BK = pages_per_block(PS) * PS
#: the parity case's rows: a page boundary either side (ps − 1, ps), a
#: third page (2·ps + 3), one that ends mid-way through its second key
#: block, and one longer than two key blocks
PARITY_LENS = [PS - 1, PS, 2 * PS + 3, BK + BK // 2 + 5, 2 * BK + PS + 3]


def _random_paged_case(rng, dtype, t, lens, ps=8, n_pages=12):
    """Pool + permuted page tables + ragged tpos for len(lens) rows.

    Pages are allocated out of order (physical != logical) and unused table
    slots point at the garbage page 0, exactly like the serving allocator.
    The pool holds ``n_pages`` pages, or as many as the garbage page, every
    row's pages and one page no table names take. ``tpos`` covers the last
    ``t`` positions of each row — T=1 is decode, T>1 a coalesced mixed step
    whose leading columns act as pad lanes for short rows (clamped to 0,
    masked by the causal comparison).
    """
    b, kv, hd = len(lens), 2, 16
    h = 4
    w = max(pages_for(max(lens), ps) + 1, 3)
    n_pages = max(n_pages, 2 + sum(pages_for(ln, ps) for ln in lens))
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


def blocked_gather_read(q, k_pool, v_pool, page_table, tpos, *,
                        softmax_dtype="float32", mask_mode="where",
                        k_scale=None, v_scale=None):
    """The gather read with its softmax summed in the fused walk's order.

    The same gather of each row's whole table and the same dequantization,
    then the keys folded ``BK`` at a time, per row and KV head, into a
    running max, sum and float32 accumulator (plain 2-D dots, the shapes
    the kernel's are). It walks every block of the table: a block with no
    valid key adds exactly zero. What it leaves to the kernel is the walk
    itself: the live length, the step tables, the page index maps, the
    staging and the pad rows."""
    b, t, h, hd = q.shape
    ps, kv = k_pool.shape[-3], k_pool.shape[-2]
    fmt = kvq.kv_format(k_pool, k_scale, hd)
    kg, vg = k_pool[page_table], v_pool[page_table]
    if fmt != "fp":
        kg = kvq.dequantize_kv(kg, k_scale[page_table], fmt, q.dtype)
        vg = kvq.dequantize_kv(vg, v_scale[page_table], fmt, q.dtype)
    bk = pages_per_block(ps) * ps
    n_blk = -(-page_table.shape[1] * ps // bk)
    pad = ((0, 0), (0, n_blk * bk - page_table.shape[1] * ps), (0, 0), (0, 0))
    kg = jnp.pad(kg.reshape(b, -1, kv, hd), pad)
    vg = jnp.pad(vg.reshape(b, -1, kv, hd), pad)
    qg = group_queries(q, kv)
    tq = jnp.repeat(tpos, h // kv, axis=1)[..., None]
    sd = jnp.dtype(softmax_dtype)
    neg = jnp.asarray(NEG_INF, sd)
    out = np.zeros(qg.shape, np.float32)
    for r, j in np.ndindex(b, kv):
        m = jnp.full((qg.shape[2], 1), NEG_INF, jnp.float32)
        den = jnp.zeros((qg.shape[2], 1), jnp.float32)
        acc = jnp.zeros((qg.shape[2], hd), jnp.float32)
        for i in range(n_blk):
            keys = slice(i * bk, (i + 1) * bk)
            valid = i * bk + jnp.arange(bk)[None, :] <= tq[r]
            s = (jax.lax.dot_general(qg[r, j], kg[r, keys, j],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 / (hd ** 0.5)).astype(sd)
            if mask_mode == "additive":
                s = s + jnp.where(valid, jnp.asarray(0.0, sd), neg)
            else:
                s = jnp.where(valid, s, neg)
            s = s.astype(jnp.float32)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p, alpha = jnp.exp(s - m_new), jnp.exp(m - m_new)
            m = m_new
            den = alpha * den + jnp.sum(p, axis=-1, keepdims=True)
            v = vg[r, keys, j]
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        out[r, j] = acc / jnp.where(den == 0, 1.0, den)
    return ungroup_queries(jnp.asarray(out).astype(q.dtype), t)


def assert_within_ulps(ref, out):
    """|out − ref| ≤ 2 ulps of the output dtype at the largest reference
    magnitude (``chip_smoke.py`` holds the chip to 4 bf16 ulps).

    Against :func:`blocked_gather_read` the fused read reads up to 3/8 of
    a float32 ulp over 3 seeds of the parity cases (fp, int8 and int4
    pages) and 0 of a bfloat16 one: what is left is the dots' rounding."""
    eps = float(jnp.finfo(out.dtype).eps)
    ref, out = (np.asarray(a, np.float32) for a in (ref, out))
    assert np.isfinite(out).all()
    top = float(np.max(np.abs(ref)))
    ulp = eps * 2.0 ** np.floor(np.log2(top))
    diff = float(np.max(np.abs(out - ref)))
    assert diff <= 2 * ulp, f"{diff} > 2 x {ulp}"


@pytest.mark.parametrize("mask_mode", ["where", "additive"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bitwise_equals_gather(dtype, t, mask_mode):
    """Page- and block-boundary lengths (``PARITY_LENS``: rows ending
    mid-page, mid-block and past two key blocks), permuted physical pages,
    garbage column present: fused == gather within 2 ulps of the output
    dtype (``assert_within_ulps``) at float32 softmax, for decode and ragged
    mixed steps. Not bitwise: the online softmax sums each row's keys block
    by block, so the gather read it is held to sums in that order too
    (``blocked_gather_read``)."""
    rng = np.random.default_rng(0)
    q, ck, cv, tbl, tpos = _random_paged_case(rng, dtype, t, PARITY_LENS,
                                              ps=PS)
    kw = dict(softmax_dtype="float32", mask_mode=mask_mode)
    assert_within_ulps(blocked_gather_read(q, ck, cv, tbl, tpos, **kw),
                       paged_attention(q, ck, cv, tbl, tpos, **kw))


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


@pytest.mark.parametrize("t", [1, 4])
def test_fused_output_invariant_to_table_width(t):
    """Garbage columns appended to the table, ``tpos`` as it was: the output
    is bitwise unchanged, so the walk stops at each row's live blocks and
    its work does not grow with the table."""
    rng = np.random.default_rng(3)
    q, ck, cv, tbl, tpos = _random_paged_case(rng, jnp.float32, t,
                                              PARITY_LENS, ps=PS)
    out = paged_attention(q, ck, cv, tbl, tpos)
    extra = jnp.full((tbl.shape[0], 3 * pages_per_block(PS)), GARBAGE_PAGE,
                     jnp.int32)
    wide = paged_attention(q, ck, cv, jnp.concatenate([tbl, extra], 1), tpos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(wide))


@pytest.mark.parametrize("t", [1, 4])
def test_fused_never_reads_past_last_live_block(t):
    """Every table slot past a row's last live key block names a NaN-filled
    page: the output is bitwise what it is with garbage there, so no page
    beyond the last live block is read. (Slots inside the last block but
    past the row's pages are read and masked: they hold the garbage page,
    finite junk, as the poison tests above require.)"""
    rng = np.random.default_rng(4)
    q, ck, cv, tbl, tpos = _random_paged_case(rng, jnp.float32, t,
                                              PARITY_LENS, ps=PS)
    ppb = pages_per_block(PS)
    tbl = np.concatenate(
        [np.asarray(tbl), np.full((tbl.shape[0], 2 * ppb), GARBAGE_PAGE,
                                  np.int32)], 1)
    out = paged_attention(q, ck, cv, jnp.asarray(tbl), tpos)
    nan_page = ck.shape[0]
    poisoned = tbl.copy()
    for i, ln in enumerate(PARITY_LENS):
        poisoned[i, -(-ln // BK) * ppb:] = nan_page
    nan = jnp.full((1,) + ck.shape[1:], jnp.nan, ck.dtype)
    got = paged_attention(q, jnp.concatenate([ck, nan]),
                          jnp.concatenate([cv, nan]), jnp.asarray(poisoned),
                          tpos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(got))


@pytest.mark.parametrize("t", [1, 4])
def test_fused_pad_rows_write_zeros(t):
    """Pad rows (every position in the garbage column, an all-garbage
    table) walk nothing and write zeros; a trailing pad query of a real row
    is finite; the real queries' outputs are bitwise those of the batch
    without them."""
    rng = np.random.default_rng(5)
    q, ck, cv, tbl, tpos = _random_paged_case(rng, jnp.float32, t,
                                              [PS + 3, BK + 5], ps=PS)
    w = tbl.shape[1]
    pad = (w - 1) * PS
    real = np.asarray(paged_attention(q, ck, cv, tbl, tpos))
    q_pad = jnp.asarray(rng.standard_normal((2,) + q.shape[1:]), q.dtype)
    out = np.asarray(paged_attention(
        jnp.concatenate([q, q_pad]), ck, cv,
        jnp.concatenate([tbl, jnp.full((2, w), GARBAGE_PAGE, jnp.int32)]),
        jnp.concatenate([tpos, jnp.full((2, t), pad, jnp.int32)])))
    np.testing.assert_array_equal(out[:2], real)
    assert not np.any(out[2:])
    if t > 1:  # the last query of each real row becomes a pad query
        out = np.asarray(paged_attention(q, ck, cv, tbl,
                                         tpos.at[:, -1].set(pad)))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[:, :-1], real[:, :-1])


@pytest.mark.parametrize("n_blk", [[4], [4, 4], [2, 0], [0, 0],
                                   [1, 3, 4, 0, 0]])
def test_walk_tables_cover_the_step_after_the_last(n_blk):
    """The grid's step tables: one step per live block of each row, row by
    row, one for a pad row, whose copies name the pages of the last row
    that walks a block. The compiled pipeline reads the index maps of the
    step after the one it runs, also after the last step, so every table
    runs one entry past the most steps a call can take, in range (where
    every row walks the most blocks, as long rag rows do, the last step
    is the tables' last but one entry)."""
    from repro.kernels.paged_attention import _walk

    n_max, b = 4, len(n_blk)
    row, blk, prow, pblk = map(np.asarray, _walk(
        jnp.asarray(n_blk, jnp.int32), n_max))
    steps = [(r, k) for r, n in enumerate(n_blk) for k in range(max(n, 1))]
    assert len(row) == len(prow) == b * n_max + 1
    assert list(zip(row[:len(steps)], blk[:len(steps)])) == steps
    assert ((0 <= row) & (row < b) & (0 <= prow) & (prow < b)).all()
    assert ((0 <= pblk) & (pblk < n_max)).all()
    last = max([r for r, n in enumerate(n_blk) if n] or [0])
    for s, (r, k) in enumerate(steps):
        want = (r, k) if k < n_blk[r] else (last, max(n_blk[last] - 1, 0))
        assert (prow[s], pblk[s]) == want


def test_sched_attn_pages_counts_live_and_table_slots():
    """``sched_attn_pages{phase, kind}``: per phase, ``live`` is the pages
    named by the rows of every call (each real row's page list; pad rows
    and unused slots hold the garbage page) and ``table`` is rows × W."""
    cfg = _smoke_cfg()
    rng = np.random.default_rng(8)
    eng = ServeEngine(cfg, init_model(KEY, cfg), batch_size=3, max_len=48,
                      page_size=8, prefill_chunk=8)
    rt = eng._rt
    real = rt._dispatch
    seen = {"prefill": [0, 0], "decode": [0, 0]}

    def dispatch(name, phase, fn, *args):
        table = np.asarray(args[4])
        seen[phase][0] += int(np.sum(table != GARBAGE_PAGE))
        seen[phase][1] += table.size
        return real(name, phase, fn, *args)

    rt._dispatch = dispatch
    for uid, n in enumerate((5, 19, 11, 30)):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, n),
                           max_new_tokens=MAX_NEW))
    eng.run()
    counter = eng.obs.registry.get("sched_attn_pages")
    for phase, (live, table) in seen.items():
        assert live > 0 and table > live
        assert counter.value(phase=phase, kind="live") == live
        assert counter.value(phase=phase, kind="table") == table
    assert table % rt.W == 0


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

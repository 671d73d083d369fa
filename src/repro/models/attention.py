"""GQA attention with RoPE/M-RoPE, qk-norm, KV caches (dense-slot KVCache or
page-table-indexed PagedKVCache), flash-style chunking."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.engine import PackedWeights as _Packed
from repro.core.engine import da_qkv_matmul
from repro.core.linear import dense
from repro.launch.sharding import constrain, paged_cache_axes
from repro.models.config import ModelConfig
from repro.models import kv_quant as _kvq
from repro.models.layers import apply_rope, rms_norm_headwise, rope_angles

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Static-shape decode cache. k/v: [B, S_max, n_kv, hd]; length: scalar."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # int32 tokens already written

    @staticmethod
    def zeros(cfg: ModelConfig, batch: int, max_len: int, dtype):
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
        return KVCache(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            length=jnp.zeros((), dtype=jnp.int32),
        )


class PagedKVCache(NamedTuple):
    """Paged decode cache for one attention layer (or a period stack).

    k/v: ``[n_pages, page_size, n_kv, hd]`` — batch-free; rows of a request
    live on the physical pages its page table names. The host-side pool
    allocator / page tables / defrag live in ``repro.serve.kvcache``; this
    container sits beside :class:`KVCache` because attention indexes it.

    Quantized pools (``kv_dtype`` int8/int4) store int8 codes in k/v (int4
    packs two nibbles per byte along hd) and carry per-(slot, head) dequant
    scales ``[n_pages, page_size, n_kv, 1]`` float16 in k_scale/v_scale —
    rank-4 pool leaves like k/v, so every page-granular pool operation
    (remap, COW copy, defrag, sharding) moves scales together with values
    with zero special-casing.  Unquantized pools leave the scales ``None``
    (an empty pytree subtree: today's layout, byte-for-byte).  Pages are
    self-describing — readers infer the format from the arrays via
    :func:`repro.models.kv_quant.kv_format`, never from config plumbing.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @staticmethod
    def zeros(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
              kv_dtype: str = "fp16"):
        hd = cfg.head_dim_
        if kv_dtype not in _kvq.KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; expected one "
                             f"of {_kvq.KV_DTYPES}")
        if kv_dtype == "fp16":  # escape hatch: compute-dtype pages, no scales
            shape = (n_pages, page_size, cfg.n_kv_heads, hd)
            return PagedKVCache(k=jnp.zeros(shape, dtype=dtype),
                                v=jnp.zeros(shape, dtype=dtype))
        if kv_dtype == "int4" and hd % 2:
            raise ValueError(
                f"kv_dtype='int4' packs two nibbles per byte along head_dim; "
                f"head_dim={hd} is odd and cannot pack")
        hd_p = hd // 2 if kv_dtype == "int4" else hd
        shape = (n_pages, page_size, cfg.n_kv_heads, hd_p)
        sshape = (n_pages, page_size, cfg.n_kv_heads, 1)
        return PagedKVCache(
            k=jnp.zeros(shape, dtype=jnp.int8),
            v=jnp.zeros(shape, dtype=jnp.int8),
            k_scale=jnp.zeros(sshape, dtype=_kvq.KV_SCALE_DTYPE),
            v_scale=jnp.zeros(sshape, dtype=_kvq.KV_SCALE_DTYPE),
        )

    @property
    def page_size(self) -> int:
        return self.k.shape[-3]


def init_attention(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype()
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = 1.0 / (d ** 0.5)
    so = 1.0 / (qd ** 0.5)
    p = {
        "wq": (jax.random.normal(ks[0], (d, qd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, kvd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, kvd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (qd, d)) * so).astype(dt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((qd,), dtype=dt)
        p["bk"] = jnp.zeros((kvd,), dtype=dt)
        p["bv"] = jnp.zeros((kvd,), dtype=dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim_,), dtype=dt)
        p["k_norm"] = jnp.ones((cfg.head_dim_,), dtype=dt)
    return p


def _fusable_qkv(*ws) -> bool:
    """The q/k/v artifacts can share one DA pass: all PackedWeights, 2-D,
    one DAConfig, one contraction dim (always true for a frozen attention
    layer; MoE-stacked or mixed float/packed params fall back)."""
    return (
        all(isinstance(w, _Packed) and w.wq.ndim == 2 for w in ws)
        and len({w.cfg for w in ws}) == 1
        and len({w.k for w in ws}) == 1
    )


def _project_qkv(p, x, cfg: ModelConfig, positions):
    b, t, _ = x.shape
    hd = cfg.head_dim_
    if _fusable_qkv(p["wq"], p["wk"], p["wv"]):
        # Frozen DA layer: quantize/decompose the activations once and run
        # the three projections as one fused engine pass (bit-identical to
        # the separate dense() calls — see da_qkv_matmul).
        yq, yk, yv = da_qkv_matmul(x, (p["wq"], p["wk"], p["wv"]))
        q = yq.astype(x.dtype) + (p.get("bq", 0))
        k = yk.astype(x.dtype) + (p.get("bk", 0))
        v = yv.astype(x.dtype) + (p.get("bv", 0))
    else:
        q = dense(x, p["wq"]) + (p.get("bq", 0))
        k = dense(x, p["wk"]) + (p.get("bk", 0))
        v = dense(x, p["wv"]) + (p.get("bv", 0))
    q = q.reshape(b, t, cfg.n_heads, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = v.reshape(b, t, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_headwise(k, p["k_norm"], cfg.norm_eps)
    ang = rope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    q = apply_rope(q, ang)
    k = apply_rope(k, ang)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def _repeat_kv(k, n_heads: int):
    """[B,S,Kv,hd] → [B,S,H,hd]. Materializing full heads keeps the score
    tensor [B,H,T,S] cleanly shardable on the 16-way model axis (H divides;
    the raw kv-head count usually doesn't) — train/prefill only; decode keeps
    the grouped form to avoid inflating KV-cache reads."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


def group_queries(q, n_kv: int):
    """[B,T,H,hd] → [B,Kv,T·G,hd]: each KV head's query rows, ordered
    (t, g) — the layout both paged-attention reads contract per head."""
    b, t, h, hd = q.shape
    g = h // n_kv
    return q.reshape(b, t, n_kv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, n_kv, t * g, hd)


def ungroup_queries(o, t: int):
    """Inverse of :func:`group_queries`: [B,Kv,T·G,hd] → [B,T,H,hd]."""
    b, kv, tg, hd = o.shape
    g = tg // t
    return o.reshape(b, kv, t, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, t, kv * g, hd)


def _gqa_scores(q, k):
    """q: [B,T,H,hd], k: [B,S,Kv,hd] → float32 scores [B,Kv,T·G,S]
    (H = Kv·G, rows as in :func:`group_queries`), accumulated in float32.

    Keys are contracted head-major (``[B,Kv,S,hd]``), the per-head
    ``[T·G, hd] x [S, hd]`` form the fused kernel runs, so on one backend
    the two reads compute each score identically."""
    hd = q.shape[-1]
    kv = k.shape[2]
    return jnp.einsum("bkqd,bksd->bkqs", group_queries(q, kv),
                      k.transpose(0, 2, 1, 3),
                      preferred_element_type=jnp.float32) / (hd ** 0.5)


def _gqa_out(probs, v, t: int):
    """probs: [B,Kv,T·G,S], v: [B,S,Kv,hd] → [B,T,H,hd] in probs' dtype,
    accumulated in float32."""
    out = jnp.einsum("bkqs,bksd->bkqd", probs, v.transpose(0, 2, 1, 3),
                     preferred_element_type=jnp.float32).astype(probs.dtype)
    return ungroup_queries(out, t)


def _query_row_mask(mask, n_groups: int):
    """[B,T,S] key-validity mask → [B,1,T·G,S], broadcast over KV heads."""
    return jnp.repeat(mask, n_groups, axis=1)[:, None]


def _masked_softmax(scores, mask, softmax_dtype, mask_mode: str):
    """Mask + softmax with the §Perf levers, cfg-free (the engine's
    paged-attention backends pass the levers as plain arguments).

    L3a additive: one fused add of a ±0/−inf bias instead of compare+select
    (one fewer full-tensor pass, no bool materialization).
    L3b softmax_dtype: bf16 score pipeline halves every pass's bytes; the
    row-max subtraction keeps it stable (|exp arg| ≤ ~40 in bf16)."""
    sd = jnp.dtype(softmax_dtype)
    scores = scores.astype(sd)
    if mask_mode == "additive":
        bias = jnp.where(mask, jnp.array(0.0, sd), jnp.array(NEG_INF, sd))
        scores = scores + bias
    else:
        scores = jnp.where(mask, scores, jnp.array(NEG_INF, sd))
    return jax.nn.softmax(scores, axis=-1)


def _apply_mask_softmax(scores, mask, cfg: ModelConfig):
    return _masked_softmax(scores, mask, cfg.softmax_dtype, cfg.attn_mask_mode)


def _decode_attention(q, k, v, mask, cfg: ModelConfig):
    """Grouped GQA attention over the cache (decode: T small)."""
    g = q.shape[2] // k.shape[2]
    scores = _gqa_scores(q, k)
    probs = _apply_mask_softmax(scores, _query_row_mask(mask, g),
                                cfg).astype(q.dtype)
    return _gqa_out(probs, v, q.shape[1])


def _naive_attention(q, k, v, mask, cfg: ModelConfig):
    """Full-head attention. q [B,T,H,hd], k/v [B,S,Kv,hd]; mask [..,T,S]."""
    h, hd = q.shape[2], q.shape[3]
    kf = _repeat_kv(k, h)
    vf = _repeat_kv(v, h)
    scores = jnp.einsum("bthd,bshd->bhts", q, kf) / (hd ** 0.5)
    probs = _apply_mask_softmax(scores, mask, cfg).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, vf)


def causal_bias(t: int, dtype=jnp.float32) -> jax.Array:
    """Additive causal bias [T, T], built ONCE per step (L8): inside the
    layer scan GSPMD re-materializes it with a 4 GB all-gather per layer;
    hoisted, it is computed/gathered once and reused by every layer."""
    pos = jnp.arange(t)
    bias = jnp.where(pos[None, :] <= pos[:, None], 0.0, NEG_INF)
    return constrain(bias.astype(dtype), (None, None))


def _lean_attention(q, k, v, cfg: ModelConfig, bias):
    """§Perf lever L8: structurally minimal causal attention.

      * pre-scales q (the 1/√d multiply lands on the small [B,T,H,hd] tensor),
      * ONE hoisted additive causal bias (no per-layer mask construction),
      * max/sub-exp/sum,
      * the 1/l normalization lands on the [B,T,H,hd] *output* (S× smaller).
    """
    h, hd = q.shape[2], q.shape[3]
    kf = _repeat_kv(k, h)
    vf = _repeat_kv(v, h)
    qs = (q * (hd ** -0.5)).astype(q.dtype)
    scores = jnp.einsum("bthd,bshd->bhts", qs, kf).astype(jnp.float32)
    scores = scores + bias[None, None]
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p.astype(q.dtype), vf)
    return o / l.transpose(0, 2, 1)[..., None].astype(o.dtype)


def _chunked_attention(q, k, v, q_offset: int, chunk: int, unroll: bool = False):
    """Flash-style online-softmax over KV chunks (pure JAX, differentiable).

    Causal: query at absolute position q_offset+i attends to kv ≤ that pos.
    Full-head form (kv repeated) so every tensor shards on the heads axis.
    """
    b, t, h, hd = q.shape
    kf = _repeat_kv(k, h)
    vf = _repeat_kv(v, h)
    s = kf.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = kf.shape[1] // chunk
    kc = kf.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = vf.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(t)

    def body(carry, xs):
        m, l, acc = carry
        ci, kb, vb = xs
        kpos = ci * chunk + jnp.arange(chunk)
        sc = jnp.einsum("bthd,bshd->bhts", q, kb).astype(jnp.float32)
        sc = sc / (hd ** 0.5)
        valid = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < s)
        sc = jnp.where(valid[None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhts,bshd->bhtd", p.astype(q.dtype), vb)
        acc_new = acc * corr[..., None].astype(acc.dtype) + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, t), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, t), dtype=jnp.float32)
    a0 = jnp.zeros((b, h, t, hd), dtype=q.dtype)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc),
        unroll=n_chunks if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-20)[..., None].astype(acc.dtype)
    return out.transpose(0, 2, 1, 3)  # [B,T,H,hd]


def paged_gather_read(q, k_pool, v_pool, page_table, tpos, *,
                      softmax_dtype="float32", mask_mode: str = "where",
                      k_scale=None, v_scale=None, layer=None):
    """Gather-based paged-attention read (the ``"gather"`` engine backend).

    Gathers each row's page table back into a contiguous ``[B, S, kv, hd]``
    view of the batch-free pool and runs masked grouped-GQA attention over
    it — the XLA-native execution the fused Pallas kernel is measured
    against. ``kpos <= tpos`` masks unwritten cache, pad lanes and the
    garbage column in one comparison.

    A 5-D pool is the period stack ``[L, n_pages, ps, kv, hd]``: ``layer``
    joins the page table as one more gather index, so no layer's pool is
    sliced out first.

    Quantized pools pass the in-page scales (``[(L,) P, ps, kv, 1]``); the
    codes and their scales ride the SAME gather and dequantize elementwise
    (``kv_quant.dequantize_kv``) before the unchanged attention math — an
    elementwise map commutes with the gather, so each gathered element is
    bitwise the value the fused kernel dequantizes in-register.
    """
    b = q.shape[0]
    fmt = _kvq.kv_format(k_pool, k_scale, q.shape[-1])
    idx = page_table if layer is None else (layer, page_table)
    kg = k_pool[idx]                   # [B, W, ps, kv, hd(/2 for int4)]
    vg = v_pool[idx]
    if fmt != "fp":
        kg = _kvq.dequantize_kv(kg, k_scale[idx], fmt, q.dtype)
        vg = _kvq.dequantize_kv(vg, v_scale[idx], fmt, q.dtype)
    kg = kg.reshape(b, -1, kg.shape[-2], kg.shape[-1])
    vg = vg.reshape(b, -1, vg.shape[-2], vg.shape[-1])
    kg = constrain(kg, ("batch", "kv_seq", "kv_heads", "head_dim"))
    vg = constrain(vg, ("batch", "kv_seq", "kv_heads", "head_dim"))
    kpos = jnp.arange(kg.shape[1])
    mask = kpos[None, None, :] <= tpos[:, :, None]    # [B, T, S] causal+length
    g = q.shape[2] // kg.shape[2]
    scores = _gqa_scores(q, kg)
    probs = _masked_softmax(scores, _query_row_mask(mask, g), softmax_dtype,
                            mask_mode).astype(q.dtype)
    return _gqa_out(probs, vg, q.shape[1])


def _paged_attention(q, k, v, cache: PagedKVCache, page_table, tpos,
                     cfg: ModelConfig, layer=None):
    """Page-table-indexed cache write + backend-dispatched attention read.

    Writes each token's K/V row at ``(page_table[b, pos // ps], pos % ps)``
    in the batch-free pool, then runs the attention read through the engine's
    paged-attention backend registry — ``cfg.paged_attn`` picks the XLA
    gather read or the fused Pallas page-walk kernel (``"auto"`` defers to
    the autotune cost table / platform heuristic per shape bucket). One code
    path serves decode (T=1), chunked prefill (T=chunk, earlier chunks
    visible through the pool) and any coalesced mix — pad lanes carry
    positions inside the garbage column, whose logical positions exceed
    every real ``tpos``, so ``kpos <= tpos`` masks them out of real rows
    exactly as it masks unwritten cache beyond a row's length.

    ``layer`` given, ``cache`` is the whole period stack
    ``[L, n_pages, ps, kv, hd]`` (the layer scan carries it): the rows
    scatter at ``[layer, page, slot]`` and the read takes the stack and the
    layer, so the layer scan updates the pool in place and never copies a
    layer's pool out or back. Without it, ``cache`` is one layer's pool.
    """
    from repro.core.engine import get_attn_backend, select_attn_backend

    b, t = tpos.shape
    ps = cache.page_size
    fmt = _kvq.kv_format(cache.k, cache.k_scale, q.shape[-1])
    b_idx = jnp.arange(b)[:, None]
    page_ids = page_table[b_idx, tpos // ps]          # [B, T] physical pages
    off = tpos % ps
    at = (page_ids, off) if layer is None else (layer, page_ids, off)
    pool_axes = paged_cache_axes(cache.k.ndim)
    if fmt == "fp":
        ck = cache.k.at[at].set(k.astype(cache.k.dtype))
        cv = cache.v.at[at].set(v.astype(cache.v.dtype))
        cks = cvs = None
    else:
        # quantize at scatter time: each row gets its own per-head absmax
        # scale (write-once — see repro.models.kv_quant), and the scales
        # scatter to the same (page, slot) the codes do
        qk, sk = _kvq.quantize_kv(k, fmt)
        qv, sv = _kvq.quantize_kv(v, fmt)
        ck = cache.k.at[at].set(qk)
        cv = cache.v.at[at].set(qv)
        cks = cache.k_scale.at[at].set(sk)
        cvs = cache.v_scale.at[at].set(sv)
        cks = constrain(cks, pool_axes)
        cvs = constrain(cvs, pool_axes)
    ck = constrain(ck, pool_axes)
    cv = constrain(cv, pool_axes)
    new_cache = PagedKVCache(k=ck, v=cv, k_scale=cks, v_scale=cvs)
    name = select_attn_backend(getattr(cfg, "paged_attn", "auto"),
                               batch=b, t=t,
                               kv_len=page_table.shape[1] * ps)
    y = get_attn_backend(name).fn(
        q, ck, cv, page_table, tpos,
        softmax_dtype=cfg.softmax_dtype, mask_mode=cfg.attn_mask_mode,
        k_scale=cks, v_scale=cvs, layer=layer,
    )
    return y, new_cache


def attention_forward(
    p,
    x,
    cfg: ModelConfig,
    positions,
    cache: Optional[KVCache] = None,
    update_cache: bool = False,
    attn_bias: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
):
    """Train fwd (cache=None), prefill (update_cache), or decode (T small,
    cache holds the past). Returns (y, new_cache).

    ``layer``: with a PagedKVCache, the cache is the period-stacked pool and
    this is the layer's index in it (see :func:`_paged_attention`)."""
    b, t, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    new_cache = None
    if isinstance(cache, PagedKVCache):
        if page_table is None:
            raise ValueError("a PagedKVCache requires a page_table operand")
        tpos = positions[..., 0] if positions.ndim == 3 else positions  # [B,T]
        y, new_cache = _paged_attention(q, k, v, cache, page_table, tpos, cfg,
                                        layer=layer)
        y = dense(y.reshape(b, t, cfg.q_dim), p["wo"])
        return constrain(y, ("batch", "seq", "embed")), new_cache
    if cache is not None:
        # Position-driven cache writes: each batch row writes its own segment
        # (continuous batching → ragged per-slot lengths). positions[..., 0]
        # is the temporal coordinate under M-RoPE.
        tpos = positions[..., 0] if positions.ndim == 3 else positions  # [B,T]
        if cfg.cache_mode == "slice":
            # L9: uniform positions — dynamic_update_slice at a scalar start
            # is GSPMD-local; the per-row scatter below makes the partitioner
            # all-gather the full-batch update per layer.
            start = tpos[0, 0]
            ck = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, start, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, start, 0, 0)
            )
        else:
            b_idx = jnp.arange(b)[:, None]
            ck = cache.k.at[b_idx, tpos].set(k.astype(cache.k.dtype))
            cv = cache.v.at[b_idx, tpos].set(v.astype(cache.v.dtype))
        ck = constrain(ck, ("batch", "kv_seq", "kv_heads", "head_dim"))
        cv = constrain(cv, ("batch", "kv_seq", "kv_heads", "head_dim"))
        new_cache = KVCache(k=ck, v=cv, length=cache.length + t)
        if update_cache and t > 1:
            # Prefill: the segment attention below sees ONLY the fresh
            # segment, so it is correct iff the cache was empty. A second
            # chunk against a warm dense cache would silently attend past
            # nothing before itself — error instead of returning garbage.
            # (cache.length is concrete on the eager path; under jit it is
            # a tracer and the fresh-cache invariant is the caller's
            # contract, as in the slot runtime's in-trace prefill.)
            try:
                warm = int(cache.length) > 0
            except (jax.errors.ConcretizationTypeError,
                    jax.errors.TracerIntegerConversionError, TypeError):
                warm = False
            if warm:
                raise ValueError(
                    "chunked prefill into a warm dense KVCache is not "
                    f"supported: the cache already holds {int(cache.length)} "
                    "tokens the fresh-segment attention cannot see. Prefill "
                    "the whole prompt in one call, or use the paged runtime "
                    "(PagedKVCache), whose attention read covers earlier "
                    "chunks through the page pool."
                )
        else:
            # decode: attend over the whole cache with a per-row length mask
            s = ck.shape[1]
            kpos = jnp.arange(s)
            mask = kpos[None, None, :] <= tpos[:, :, None]  # [B,T,S]
            y = _decode_attention(q, ck, cv, mask, cfg)
            y = dense(y.reshape(b, t, cfg.q_dim), p["wo"])
            return constrain(y, ("batch", "seq", "embed")), new_cache

    # train / prefill self-attention over the current segment
    if cfg.attn_impl == "lean":
        bias = attn_bias if attn_bias is not None else causal_bias(t)
        y = _lean_attention(q, k, v, cfg, bias)
    elif cfg.attn_chunk_q and t > cfg.attn_chunk_q:
        y = _chunked_attention(
            q, k, v, q_offset=0, chunk=cfg.attn_chunk_q, unroll=cfg.scan_unroll
        )
    else:
        tpos_c = jnp.arange(t)
        mask = (tpos_c[None, :] <= tpos_c[:, None])[None, None]  # [1,1,T,S]
        y = _naive_attention(q, k, v, mask, cfg)
    y = dense(y.reshape(b, t, cfg.q_dim), p["wo"])
    return constrain(y, ("batch", "seq", "embed")), new_cache

"""Fused Pallas paged-attention read: a walk over each row's live key blocks.

The serving gather path (``models/attention.paged_gather_read``) re-creates
the full ``[B, W*ps, kv, hd]`` KV view from the batch-free page pool every
step — an HBM gather whose traffic dwarfs the attention math at decode
shapes. This kernel walks the page table ON-CHIP instead, and only as far
as each row's context reaches.

The walk: a row's keys come in blocks of ``pages_per_block(ps)`` pages
(``BLOCK_KEYS`` keys: 8 pages of 16), and the grid takes one step per live
block of each row, row after row, its length read from the data (a
dynamic grid). Each page of a block is its own input, whose BlockSpec index
map reads the scalar-prefetched page table, so a step copies its block's
pages into VMEM while the step before computes (Pallas double-buffers the
copies). Pages the table does not name, and blocks past a row's live keys,
are never copied, and the dense ``[B, S, kv, hd]`` view never exists in
HBM.

Live length: the last table column is the garbage column, where
``kvcache.pad_position`` puts every pad query. A row's live key count is
``1 + max{tpos[b, t] : tpos[b, t] < (W − 1)·ps}``, 0 when it has no such
position (a pad row), computed from ``tpos`` inside the call. A pad row
takes one step that copies nothing new (it names the pages the step before
it copied), computes nothing and writes zeros; a pad query of a live row
attends over the row's live keys.

The pool may be the whole period stack ``[L, n_pages, ps, kv, hd]`` with
the layer as a scalar-prefetch operand: the page index maps read
``(layer, table[row, slot])``, so the model's layer scan hands the kernel
the stacked pool it updates in place and no layer's pool is ever sliced
out. A 4-D ``[n_pages, ps, kv, hd]`` pool is the one-layer case. Quantized
pools (int8 codes, or int4 nibble pairs packed along hd) copy each page's
``[ps, kv, 1]`` scale sliver through the same index map as its codes and
dequantize the block in-register, a page at a time, with
``kv_quant.dequantize_kv``, the gather read's formula.

Numerics: an online softmax. Per KV head the kernel keeps a running max, a
running sum and a float32 ``[T·G, hd]`` accumulator in VMEM; each block
computes its ``[T·G, hd] x [BK, hd]`` scores for the head's group of query
heads (float32 on the MXU, then rounded to ``softmax_dtype``), masks them
(key position ``s`` counts for query ``t`` iff ``s <= tpos[b, t]``),
rescales the running state and adds its float32 PV product; the row's
output is ``acc / sum``. A query with no valid key in a block adds exactly
zero (``NEG_INF`` is the finite -1e30, so every exp stays finite). The sums
run in another order than the gather read's softmax over the whole table,
so the two reads are no longer bitwise equal, in interpret mode or on a
TPU: the tests hold the fused read within 2 ulps of the output dtype, at
the largest output, of the gather read summed in the same block order, and
``chip_smoke.py`` to 4 bf16 ulps of the gather read on the chip.

VMEM holds the block's pages twice (the pipeline's two buffers), the
staged ``[BK, kv, hd]`` K and V blocks and the per-head statistics:
independent of the table width, so of ``max_len``.

``interpret=None`` derives the execution mode from the backend platform:
compiled on TPU, interpreter everywhere else (the CPU CI correctness path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models import kv_quant as _kvq
from repro.models.attention import group_queries, ungroup_queries

# Matches models/attention.NEG_INF so masked lanes are bit-identical.
NEG_INF = -1e30

#: Keys per block of the walk (whole pages; one page where pages are larger).
BLOCK_KEYS = 128


def pages_per_block(page_size: int) -> int:
    """Pages the walk copies and folds in together."""
    return max(1, BLOCK_KEYS // page_size)


def live_keys(tpos: jax.Array, width: int, page_size: int) -> jax.Array:
    """``[B, T]`` positions → ``[B]`` live key counts: one past the largest
    position before the garbage column (the table's last), 0 for a row
    whose every position lies in it (a pad row)."""
    lim = (width - 1) * page_size
    return 1 + jnp.max(jnp.where(tpos < lim, tpos, -1), axis=1)


def _walk(n_blk: jax.Array, n_max: int):
    """The grid's step tables for rows of ``n_blk`` live blocks: each step's
    row and block, and the row and block whose pages the step's copies
    hold. A row takes one step per live block, a pad row one step (it
    writes zeros), and a pad row's step names the pages of the last row
    that walks a block: where pad rows trail the real ones, as the
    scheduler packs them, the pipeline copies nothing for it. The tables
    run one entry past the most steps a call can take: to start the copies
    of the step after the one it runs, the pipeline reads that step's
    index maps, also after the last step. Elementwise and small, as each
    layer of a step computes them again."""
    b = n_blk.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    n_steps = jnp.maximum(n_blk, 1)
    ends = jnp.cumsum(n_steps)
    s = jnp.arange(b * n_max + 1, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= s[:, None], axis=1,
                              dtype=jnp.int32), b - 1)
    blk = s - (ends - n_steps)[row]
    last = jnp.max(jnp.where(n_blk > 0, rows, 0))
    real = blk < n_blk[row]
    return (row, blk, jnp.where(real, row, last),
            jnp.where(real, blk, jnp.maximum(n_blk[last] - 1, 0)))


def _walk_operands(page_table: jax.Array, tpos: jax.Array, page_size: int,
                   n_groups: int):
    """The walk's operands: the page table cut (or padded with its garbage
    column) to whole blocks, each row's live block count, the step tables
    of :func:`_walk`, and each grouped query row's position. A pad query's
    position (in the garbage column) is held to its row's last live key,
    so it attends over the live keys alone."""
    ppb = pages_per_block(page_size)
    bk = ppb * page_size
    w = page_table.shape[1]
    tpos = tpos.astype(jnp.int32)
    live = live_keys(tpos, w, page_size)
    n_blk = ((live + bk - 1) // bk).astype(jnp.int32)
    tpos_rows = jnp.repeat(jnp.minimum(tpos, live[:, None] - 1), n_groups,
                           axis=1)[..., None]
    n_max = -(-(w - 1) // ppb)          # live blocks never reach column W-1
    table = page_table.astype(jnp.int32)
    if n_max * ppb <= w:
        table = table[:, :n_max * ppb]
    else:  # the last block reaches past the table: repeat the garbage column
        table = jnp.concatenate(
            [table, jnp.repeat(table[:, -1:], n_max * ppb - w, axis=1)], 1)
    return table, n_blk, _walk(n_blk, n_max), tpos_rows


def _default_interpret() -> bool:
    """Platform-derived execution mode: compiled on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def _paged_attn_kernel(
    table_ref,   # scalar-prefetch: [B, W'] page table, W' whole blocks (SMEM)
    layer_ref,   # scalar-prefetch: [1] layer of the stacked pool
    nblk_ref,    # scalar-prefetch: [B] live key blocks of each row
    row_ref,     # scalar-prefetch: [S] row of each grid step
    blk_ref,     # scalar-prefetch: [S] key block of each grid step
    pgrow_ref,   # scalar-prefetch: [S] row whose block's pages the step's
    pgblk_ref,   # [S] copies hold (a pad row's step repeats the last ones)
    q_ref,       # [1, kv, T*G, hd] this row's queries, grouped by KV head
    tpos_ref,    # [1, T*G, 1] temporal position of each query row
    *refs,       # the block's K pages, V pages [, K scales, V scales]:
    #              ppb refs each, [1, ps, kv, hd(/2)] or [1, ps, kv, 1];
    #              then o_ref; then scratch: K, V block [BK, kv, hd],
    #              running max, running sum, accumulator[, the K, V code
    #              and K, V scale blocks of a quantized pool]
    page_size: int,
    pages_per_blk: int,
    softmax_dtype,
    mask_mode: str,
    kv_fmt: str,
):
    del table_ref, layer_ref, pgrow_ref, pgblk_ref  # read by the index maps
    ps, ppb = page_size, pages_per_blk
    n_in = 2 * ppb if kv_fmt == "fp" else 4 * ppb
    pages, (o_ref, k_blk, v_blk, m_scr, l_scr, acc_scr, *staged) = (
        refs[:n_in], refs[n_in:])
    bk = ppb * ps
    n_kv, tg, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    step = pl.program_id(0)
    blk = blk_ref[step]
    n_blk = nblk_ref[row_ref[step]]

    @pl.when(blk == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(blk < n_blk)
    def _fold():
        # stage the block's pages at their offsets; an int8 / int4 block is
        # staged as codes and scales, then dequantized in-register with the
        # gather read's exact formula
        for i in range(ppb):
            keys = pl.ds(i * ps, ps)
            if kv_fmt == "fp":
                k_blk[keys], v_blk[keys] = pages[i][0], pages[ppb + i][0]
            else:
                for j, dst in enumerate(staged):
                    dst[keys] = pages[j * ppb + i][0]
        if kv_fmt != "fp":
            def dequantize(i, carry):  # a page at a time: int4 unpacks wide
                keys = pl.ds(pl.multiple_of(i * ps, ps), ps)
                for dst, code, scale in ((k_blk, *staged[0::2]),
                                         (v_blk, *staged[1::2])):
                    dst[keys] = _kvq.dequantize_kv(code[keys], scale[keys],
                                                   kv_fmt, dst.dtype)
                return carry

            jax.lax.fori_loop(0, ppb, dequantize, 0)
        sd = softmax_dtype
        neg = jnp.asarray(NEG_INF, sd)
        kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (tg, bk), 1)
        valid = kpos <= tpos_ref[0]
        for j in range(n_kv):
            scores = jax.lax.dot_general(
                q_ref[0, j], k_blk[:, j, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / (hd ** 0.5)
            scores = scores.astype(sd)
            if mask_mode == "additive":
                scores = scores + jnp.where(valid, jnp.asarray(0.0, sd), neg)
            else:
                scores = jnp.where(valid, scores, neg)
            scores = scores.astype(jnp.float32)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1,
                                                keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[j] = m_new
            l_scr[j] = alpha * l_scr[j] + jnp.sum(p, axis=-1, keepdims=True)
            v_j = v_blk[:, j, :]
            acc_scr[j] = alpha * acc_scr[j] + jax.lax.dot_general(
                p.astype(v_j.dtype), v_j, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(blk == jnp.maximum(n_blk, 1) - 1)
    def _finish():
        l_all = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l_all == 0, 1.0, l_all)
                    ).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,           # [B, T, H, hd]
    k_pool: jax.Array,      # [(L,) n_pages, ps, kv, hd]  (hd//2 for int4)
    v_pool: jax.Array,      # [(L,) n_pages, ps, kv, hd]
    page_table: jax.Array,  # [B, W] int32 physical page ids
    tpos: jax.Array,        # [B, T] int32 temporal positions (pad -> pad_pos)
    *,
    softmax_dtype="float32",
    mask_mode: str = "where",
    k_scale: jax.Array | None = None,  # [(L,) n_pages, ps, kv, 1] scales
    v_scale: jax.Array | None = None,  # (quantized pools only)
    layer: jax.Array | int | None = None,  # which L of a 5-D pool
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged-attention read: returns ``[B, T, H, hd]`` context.

    Drop-in replacement for the gather read over an already-written pool
    (scatter of the current step's K/V happens before either read). The
    page walk over each row's live key blocks, ragged masking, the online
    softmax and the PV contraction all run inside one Pallas kernel; see
    the module docstring for the walk and its numerics.

    Quantized pools (int8 codes, or int4 nibble pairs packed along hd) pass
    their in-page scales: each page's ``[ps, kv, 1]`` scale sliver is copied
    with its codes, and dequantization happens in-register with the gather
    backend's exact elementwise formula.

    A 5-D pool is the period stack; ``layer`` names the layer to read, and
    only that layer's scale pool is widened for the kernel.
    """
    if k_pool.ndim == 4:  # one layer: a stack of one (a free reshape)
        if layer is not None:
            raise ValueError("layer given for a 4-D (single-layer) pool")
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    elif layer is None:
        raise ValueError("a 5-D (period-stacked) pool needs its layer index")
    b, t, h, hd = q.shape
    _, _, ps, kv, hd_p = k_pool.shape
    if h % kv:
        raise ValueError(f"n_heads={h} not divisible by n_kv_heads={kv}")
    g = h // kv
    if interpret is None:
        interpret = _default_interpret()
    kv_fmt = _kvq.kv_format(k_pool, k_scale, hd)
    ppb = pages_per_block(ps)
    bk = ppb * ps

    # Group the queries by KV head outside the kernel (the gather read's
    # layout), so every in-kernel matmul is a plain 2-D MXU pass per head.
    qg = group_queries(q, kv)
    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=ps,
        pages_per_blk=ppb,
        softmax_dtype=jnp.dtype(softmax_dtype),
        mask_mode=mask_mode,
        kv_fmt=kv_fmt,
    )
    row_spec = pl.BlockSpec((1, kv, t * g, hd),
                            lambda s, tbl, lyr, nb, row, *_: (row[s], 0, 0, 0))

    def page_spec(i, minor, one_layer=False):
        """Page ``i`` of the step's block: the table's entry for it, in the
        step's layer of the stack (or in a stack of one)."""
        return pl.BlockSpec(
            (pl.squeezed, 1, ps, kv, minor),
            lambda s, tbl, lyr, nb, row, blk, prow, pblk: (
                0 if one_layer else lyr[0], tbl[prow[s], pblk[s] * ppb + i],
                0, 0, 0))

    in_specs = [row_spec,
                pl.BlockSpec((1, t * g, 1),
                             lambda s, tbl, lyr, nb, row, *_: (row[s], 0, 0))]
    in_specs += [page_spec(i, hd_p) for i in range(ppb)] * 2
    operands = [k_pool] * ppb + [v_pool] * ppb
    if kv_fmt != "fp":
        in_specs += [page_spec(i, 1, one_layer=True) for i in range(ppb)] * 2
        # the TPU kernel compiler loads no float16: widen this layer's scale
        # pool (exact; hd× smaller than its code pool) as a stack of one
        operands += [
            jax.lax.dynamic_index_in_dim(s, layer, keepdims=True)
            .astype(jnp.float32) for s in (k_scale, v_scale)
            for _ in range(ppb)]
    # staged blocks are dequantized, so quantized pools stage at q's dtype
    stage_dtype = k_pool.dtype if kv_fmt == "fp" else q.dtype
    scratch = [
        pltpu.VMEM((bk, kv, hd), stage_dtype),
        pltpu.VMEM((bk, kv, hd), stage_dtype),
        pltpu.VMEM((kv, t * g, 1), jnp.float32),   # running max
        pltpu.VMEM((kv, t * g, 1), jnp.float32),   # running sum
        pltpu.VMEM((kv, t * g, hd), jnp.float32),  # accumulator
    ] + ([] if kv_fmt == "fp" else [
        pltpu.VMEM((bk, kv, hd_p), k_pool.dtype),
        pltpu.VMEM((bk, kv, hd_p), v_pool.dtype),
        pltpu.VMEM((bk, kv, 1), jnp.float32),
        pltpu.VMEM((bk, kv, 1), jnp.float32)])
    # named_scope: the walk's prep and the kernel show up under one
    # attributable name in profiler captures (kv format in the name
    # separates fp/int8/int4 dispatches)
    with jax.named_scope(f"paged_attn_fused_{kv_fmt}"):
        table, n_blk, steps, tpos_rows = _walk_operands(page_table, tpos,
                                                        ps, g)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=7,
                grid=(jnp.sum(jnp.maximum(n_blk, 1)),),
                in_specs=in_specs,
                out_specs=row_spec,
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((b, kv, t * g, hd), q.dtype),
            interpret=interpret,
        )(table, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), n_blk,
          *steps, qg, tpos_rows, *operands)
    return ungroup_queries(out, t)

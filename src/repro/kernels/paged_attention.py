"""Fused Pallas paged-attention read: in-kernel page-table walk.

The serving gather path (``models/attention.paged_gather_read``) re-creates
the full ``[B, W*ps, kv, hd]`` KV view from the batch-free page pool every
step — an HBM gather whose traffic dwarfs the attention math at decode
shapes. This kernel walks the page table ON-CHIP instead: the grid is
(batch row, page slot) and the K/V BlockSpec index maps read the
scalar-prefetched page table, so each grid step DMAs exactly ONE physical
page into VMEM. Pages the table does not name are never touched, and the
dense ``[B, S, kv, hd]`` view never exists in HBM.

The pool may be the whole period stack ``[L, n_pages, ps, kv, hd]`` with
the layer as a second scalar-prefetch operand: the page index map then
reads ``(layer, table[b, w])``, so the model's layer scan hands the kernel
the stacked pool it updates in place and no layer's pool is ever sliced
out. A 4-D ``[n_pages, ps, kv, hd]`` pool is the one-layer case.

Each step stages its page's K/V rows (dequantized in-register for int8 /
int4 pools) at their logical offset in a VMEM row buffer, which Pallas
persists across the sequential grid. The row's last page runs the epilogue:
per KV head, one ``[T·G, hd] x [S, hd]`` score matmul for the head's group of
query heads, the ragged mask, softmax, and the PV matmul. Both matmuls
accumulate in float32 (the MXU's accumulator).

Ragged masking happens in-kernel: key position ``s`` contributes to query
``t`` iff ``s <= tpos[b, t]``. Pad lanes point at the garbage page
(physical page 0) with ``tpos`` beyond every real position, so garbage rows
are masked out exactly as in the gather path.

Numerics match the gather path BIT-FOR-BIT in interpret mode at the default
``softmax_dtype="float32"`` (CI asserts it): every score is the same
float32-accumulated dot over ``hd`` the gather einsum computes, the
epilogue reduces over the same ``S`` keys in the same order, and it
replicates ``jax.nn.softmax``'s ``exp(x - max) / sum`` form with the same
dtypes and casts. For sub-f32 softmax dtypes (``softmax_dtype="bfloat16"``)
exact bit-parity across lowerings is not attainable in principle — XLA fuses
``exp``+``reduce`` and keeps f32 intermediates across the pair, eliding bf16
roundings an op-by-op kernel must perform — so there the kernel is within
one bf16 ulp per reduction, not bitwise. Compiled on a TPU the two reads
are two lowerings that may sum in different orders, so bit-parity there is
not guaranteed; ``chip_smoke.py`` holds them to a stated tolerance.

VMEM holds the staged K and V of one row: ``2 · W·ps · kv · hd`` elements,
linear in the table width.

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


def _default_interpret() -> bool:
    """Platform-derived execution mode: compiled on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def _paged_attn_kernel(
    table_ref,  # scalar-prefetch: [B, W] page table (SMEM)
    layer_ref,  # scalar-prefetch: [1] layer of the stacked pool (SMEM)
    q_ref,      # [1, kv, T*G, hd] this row's queries, grouped by KV head
    tpos_ref,   # [1, T*G, 1] temporal position of each query row
    k_ref,      # [1, ps, kv, hd(/2)] — ONE physical K page, chosen by table
    v_ref,      # [1, ps, kv, hd(/2)] — ONE physical V page
    *rest,      # [ks_ref, vs_ref,] o_ref, k_scr, v_scr — the scale pages
    #             [1, ps, kv, 1] ride the same table-indexed walk and are
    #             present iff the pool is quantized (kv_fmt != "fp")
    n_pages_walked: int,
    page_size: int,
    softmax_dtype,
    mask_mode: str,
    kv_fmt: str,
):
    del table_ref, layer_ref  # consumed by the BlockSpec index maps
    if kv_fmt == "fp":
        o_ref, k_scr, v_scr = rest
    else:
        ks_ref, vs_ref, o_ref, k_scr, v_scr = rest
    wi = pl.program_id(1)
    ps = page_size

    # Stage this page at its logical offset, dequantized in-register with the
    # gather read's exact elementwise formula (kv_quant.dequantize_kv).
    if kv_fmt == "fp":
        k_page, v_page = k_ref[0], v_ref[0]
    else:
        k_page = _kvq.dequantize_kv(k_ref[0], ks_ref[0], kv_fmt, q_ref.dtype)
        v_page = _kvq.dequantize_kv(v_ref[0], vs_ref[0], kv_fmt, q_ref.dtype)
    k_scr[pl.ds(wi * ps, ps)] = k_page
    v_scr[pl.ds(wi * ps, ps)] = v_page

    @pl.when(wi == n_pages_walked - 1)
    def _epilogue():
        n_kv, tg, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        s = k_scr.shape[0]
        sd = softmax_dtype
        kpos = jax.lax.broadcasted_iota(jnp.int32, (tg, s), 1)
        valid = kpos <= tpos_ref[0]
        for j in range(n_kv):
            qj = q_ref[0, j]
            scores = jax.lax.dot_general(
                qj, k_scr[:, j, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / (hd ** 0.5)
            scores = scores.astype(sd)
            neg = jnp.asarray(NEG_INF, sd)
            if mask_mode == "additive":
                scores = scores + jnp.where(valid, jnp.asarray(0.0, sd), neg)
            else:
                scores = jnp.where(valid, scores, neg)
            # jax.nn.softmax's form: exp(x - max) / sum, in softmax_dtype
            unnorm = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
            probs = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)
            out = jax.lax.dot_general(
                probs.astype(qj.dtype), v_scr[:, j, :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            o_ref[0, j] = out.astype(o_ref.dtype)


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
    page walk, ragged masking, softmax and PV contraction all run inside
    one Pallas kernel; see the module docstring for the bit-parity argument.

    Quantized pools (int8 codes, or int4 nibble pairs packed along hd) pass
    their in-page scales: the scale blocks ride the SAME scalar-prefetch
    index map as the page walk — each grid step DMAs one codes page plus
    its ``[ps, kv, 1]`` scale sliver — and dequantization happens
    in-register with the gather backend's exact elementwise formula.

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
    w = page_table.shape[1]
    if h % kv:
        raise ValueError(f"n_heads={h} not divisible by n_kv_heads={kv}")
    g = h // kv
    if interpret is None:
        interpret = _default_interpret()
    kv_fmt = _kvq.kv_format(k_pool, k_scale, hd)

    # Group the queries by KV head outside the kernel (the gather read's
    # layout), so every in-kernel matmul is a plain 2-D MXU pass per head;
    # each query row carries its own temporal position for the ragged mask.
    qg = group_queries(q, kv)
    tpos_rows = jnp.repeat(tpos.astype(jnp.int32), g, axis=1)[..., None]

    kernel = functools.partial(
        _paged_attn_kernel,
        n_pages_walked=w,
        page_size=ps,
        softmax_dtype=jnp.dtype(softmax_dtype),
        mask_mode=mask_mode,
        kv_fmt=kv_fmt,
    )
    row_spec = pl.BlockSpec((1, kv, t * g, hd),
                            lambda bi, wi, tbl, lyr: (bi, 0, 0, 0))
    # The page walk: block index = (layer, table entry for (row, slot)),
    # the layer dimension squeezed, so the kernel sees one [1, ps, kv, hd]
    # page as before.
    page_spec = pl.BlockSpec(
        (pl.squeezed, 1, ps, kv, hd_p),
        lambda bi, wi, tbl, lyr: (lyr[0], tbl[bi, wi], 0, 0, 0))
    in_specs = [
        row_spec,
        pl.BlockSpec((1, t * g, 1), lambda bi, wi, tbl, lyr: (bi, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [qg, tpos_rows, k_pool, v_pool]
    if kv_fmt != "fp":
        scale_spec = pl.BlockSpec(
            (pl.squeezed, 1, ps, kv, 1),
            lambda bi, wi, tbl, lyr: (0, tbl[bi, wi], 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        # the TPU kernel compiler loads no float16: widen this layer's scale
        # pool (exact; hd× smaller than its code pool) as a stack of one
        operands += [
            jax.lax.dynamic_index_in_dim(s, layer, keepdims=True)
            .astype(jnp.float32) for s in (k_scale, v_scale)]
    # staged rows are dequantized, so quantized pools stage at q's dtype
    stage_dtype = k_pool.dtype if kv_fmt == "fp" else q.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((w * ps, kv, hd), stage_dtype),
            pltpu.VMEM((w * ps, kv, hd), stage_dtype),
        ],
    )
    # named_scope: the kernel shows up as one attributable op in profiler
    # captures (kv format in the name separates fp/int8/int4 dispatches)
    with jax.named_scope(f"paged_attn_fused_{kv_fmt}"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, kv, t * g, hd), q.dtype),
            interpret=interpret,
        )(page_table.astype(jnp.int32),
          jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), *operands)
    return ungroup_queries(out, t)

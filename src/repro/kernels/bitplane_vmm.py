"""Pallas TPU kernel: storage-free (bit-plane) Distributed Arithmetic VMM.

The deployable DA mode for large LM layers (DESIGN.md §2): instead of reading
precomputed weight sums from a materialized LUT, the MXU computes each
bit-serial cycle's weight sums on the fly —

    Y = Σ_b coef(b) · (xbit_b @ W),   xbit_b ∈ {0,1}

which is exactly the paper's per-cycle ``MR`` with the systolic array playing
the role of the processing-memory array. Multiplications involve only the
{0,1} bit operand (multiplier-free in the DA sense); accumulation is int32.

Tiling: grid = (M/bm, N/bn, K/bk). W is streamed through VMEM as int8-ranged
[bk, bn] tiles; the input tile [bm, bk] is decomposed into its 8 bit-planes
in-register. K is the reduction axis (output revisited, init at k == 0).

Exactness: each per-tile dot is a {0,1}-plane against weight codes, so its
value is ≤ bk·max|w| — kept < 2²⁴ (fp32's exact-integer range) by shrinking
the K tile to fit the actual weight-code magnitude (:func:`_fit_bk`); the
dot runs at ``Precision.HIGHEST`` so the MXU keeps float32 products of codes
wider than bfloat16's 8-bit significand; the int32 accumulator covers the
full growth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.da import DAConfig, bit_coefs


def _bitplane_kernel(x_ref, w_ref, out_ref, *, cfg: DAConfig):
    k_idx = pl.program_id(2)
    x = x_ref[...]  # [bm, bk] int32 codes
    w = w_ref[...].astype(jnp.float32)  # [bk, bn]

    mask = (1 << cfg.x_bits) - 1
    xm = jnp.bitwise_and(x, mask)
    coefs = bit_coefs(cfg.x_bits, cfg.x_signed)

    acc = jnp.zeros(out_ref.shape, dtype=jnp.int32)
    for b in range(cfg.x_bits):  # unrolled bit-serial cycles
        plane = jnp.bitwise_and(jnp.right_shift(xm, b), 1).astype(jnp.float32)
        mr = jnp.dot(plane, w, preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
        acc = acc + jnp.int32(coefs[b]) * mr.astype(jnp.int32)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(k_idx != 0)
    def _accum():
        out_ref[...] += acc


def _default_interpret() -> bool:
    """Platform-derived execution mode: compiled on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def _weight_code_bound(wq: jax.Array, w_maxabs) -> int:
    """Magnitude bound on the weight codes, for the fp32-exact tile fit.

    Narrow integer storage (≤ 16 bits) bounds itself by dtype; wider storage
    is inspected when concrete, and must declare ``w_maxabs`` under tracing
    (the magnitude of a traced int32 operand is unknowable at trace time).
    """
    if w_maxabs is not None:
        w_maxabs = int(w_maxabs)
        if w_maxabs < 1:
            raise ValueError(f"w_maxabs={w_maxabs} must be >= 1")
        return w_maxabs
    if jnp.issubdtype(wq.dtype, jnp.integer) and jnp.iinfo(wq.dtype).bits <= 16:
        return int(jnp.iinfo(wq.dtype).max)
    if isinstance(wq, jax.core.Tracer):
        raise ValueError(
            f"bitplane_vmm_pallas: weight codes stored as {wq.dtype} under "
            "tracing — pass w_maxabs=<bound on |wq|> so the fp32-exact K "
            "tile can be sized"
        )
    return max(1, int(jnp.max(jnp.abs(wq))))


def _fit_bk(bk: int, w_maxabs: int) -> int:
    """Largest K tile ≤ bk with bk · w_maxabs < 2²⁴ (fp32-exact MXU pass)."""
    limit = (1 << 24) - 1
    if w_maxabs > limit:
        raise ValueError(
            f"weight-code magnitude {w_maxabs} exceeds the fp32 exact-integer "
            "range: no K tile keeps the bit-plane dot exact"
        )
    while bk > 1 and bk * w_maxabs > limit:
        bk //= 2
    return bk


def bitplane_vmm_pallas(
    xq: jax.Array,
    wq: jax.Array,
    cfg: DAConfig,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
    w_maxabs: int | None = None,
) -> jax.Array:
    """Bit-plane DA VMM via Pallas. xq [M,K] int codes, wq [K,N] int codes.

    Returns int32 [M, N] == xq @ wq exactly.  ``interpret=None`` derives the
    execution mode from the platform (compiled on TPU, interpret elsewhere).
    ``bk`` auto-shrinks so each {0,1}-plane dot stays within fp32's exact
    range for the actual weight-code magnitude (``w_maxabs``, defaulted from
    the storage dtype or the concrete codes).
    """
    if interpret is None:
        interpret = _default_interpret()
    bk = _fit_bk(bk, _weight_code_bound(wq, w_maxabs))
    return _bitplane_vmm_call(xq, wq, cfg, bm, bn, bk, interpret)


@functools.partial(jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret"))
def _bitplane_vmm_call(xq, wq, cfg, bm, bn, bk, interpret):
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        xq = jnp.pad(xq, ((0, pm), (0, pk)))
    if pk or pn:
        wq = jnp.pad(wq, ((0, pk), (0, pn)))
    mm, nn, kk = m + pm, n + pn, k + pk

    out = pl.pallas_call(
        functools.partial(_bitplane_kernel, cfg=cfg),
        grid=(mm // bm, nn // bn, kk // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.int32),
        interpret=interpret,
    )(xq.astype(jnp.int32), wq.astype(jnp.int32))
    return out[:m, :n]

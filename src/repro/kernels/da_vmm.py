"""Pallas TPU kernel: Distributed-Arithmetic VMM with in-VMEM LUT readout.

TPU-native mapping of the paper's PMA datapath:

  * the PMA *address decoder* (L input bits → 1-of-2^L wordline) becomes an
    MXU pass plus a compare: the bit-plane ``[bm, bg·L]`` times a constant
    0/2^i expansion matrix ``[bg·L, bg·2^L]`` places each group's address
    ``Σ_i bit_i·2^i`` on all 2^L columns of that group, and comparing with
    the column's own address gives the one-hot wordlines;
  * the *array readout + inter-PMA adder tree* becomes a single MXU matmul
    ``onehot[bm, bg·2^L] @ LUT[bg·2^L, bn]`` — the systolic array sums the
    selected weight-sum rows of every PMA group in one pass;
  * the *bit-serial shift-and-add accumulator* becomes an unrolled loop over
    the bit-planes with int32 accumulation (covers the 21-bit growth).

Tiling: grid = (M/bm, N/bn, G/bg); the LUT is streamed through VMEM in
``bg``-group chunks of shape [bg·2^L, bn]. By default ``bg·L`` is the
smallest multiple of the 128-lane vreg width (bg=16 at L=8 → a [4096, 128]
int32 LUT block, 2 MB), or all groups when K is smaller. The G axis is the
reduction dimension — the output block is revisited and accumulated,
initialized at g == 0.

Exactness: the address pass sums at most L products of a bit with a power of
two below 2^L, exact in bfloat16 operands with float32 accumulation. The
readout pass selects one LUT entry per group (|entry| ≤ L·127, past
bfloat16's 8-bit significand), so it runs at ``Precision.HIGHEST`` — float32
products — and its sums stay below bg·L·127 < 2²⁴; accumulation is int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.da import DAConfig, bit_coefs

#: Lane width of a TPU vreg: the input block's last dim is a multiple of it.
_LANES = 128


def _da_vmm_kernel(x_ref, e_ref, lut_ref, out_ref, *, cfg: DAConfig):
    """One (m, n, g) tile: bg PMA groups × all bit-planes, accumulated."""
    r = 1 << cfg.group_size
    g_idx = pl.program_id(2)

    x = x_ref[...]  # [bm, bg*L] int32 codes of this group chunk
    expand = e_ref[...]  # [bg*L, bg*R] 0 / 2^i address expansion
    lut = lut_ref[...].astype(jnp.float32)  # [bg*R, bn]

    xm = jnp.bitwise_and(x, (1 << cfg.x_bits) - 1)
    # column c of a group chunk is wordline (c mod R) of PMA group c // R
    col_addr = jnp.bitwise_and(
        jax.lax.broadcasted_iota(jnp.int32, (1, expand.shape[1]), 1), r - 1)

    coefs = bit_coefs(cfg.x_bits, cfg.x_signed)
    acc = jnp.zeros(out_ref.shape, dtype=jnp.int32)
    for b in range(cfg.x_bits):  # the bit-serial "memory cycles", unrolled
        bits = jnp.bitwise_and(jnp.right_shift(xm, b), 1).astype(expand.dtype)
        addr = jnp.dot(bits, expand, preferred_element_type=jnp.float32)
        onehot = (addr.astype(jnp.int32) == col_addr).astype(jnp.float32)
        mr = jnp.dot(onehot, lut, preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
        acc = acc + jnp.int32(coefs[b]) * mr.astype(jnp.int32)

    @pl.when(g_idx == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(g_idx != 0)
    def _accum():
        out_ref[...] += acc


def _address_expansion(bg: int, l: int) -> jax.Array:
    """[bg·L, bg·2^L] bfloat16: row ``gi·L + i`` holds 2^i on the 2^L
    columns of group gi, so ``bits @ E`` repeats each group's address over
    its wordlines."""
    r = 1 << l
    rows = jnp.arange(bg * l)[:, None]
    cols = jnp.arange(bg * r)[None, :]
    return jnp.where(rows // l == cols // r, 2 ** (rows % l), 0).astype(
        jnp.bfloat16)


def _default_group_block(num_groups_: int, group_size: int) -> int:
    """Groups per LUT block: the fewest whose inputs fill whole vreg lane
    rows (``bg·L`` a multiple of 128), or all groups when K is smaller."""
    bg = math.lcm(group_size, _LANES) // group_size
    return min(bg, num_groups_)


@functools.partial(
    jax.jit, static_argnames=("cfg", "bm", "bn", "bg", "interpret")
)
def da_vmm_pallas(
    xq: jax.Array,
    luts: jax.Array,
    cfg: DAConfig,
    bm: int = 256,
    bn: int = 128,
    bg: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """DA VMM via Pallas. xq [M, K] int32 codes; luts [G, 2^L, N] int32.

    Returns int32 [M, N] == xq @ W exactly. ``bg=None`` picks
    :func:`_default_group_block`. ``interpret=None`` derives the execution
    mode from the platform: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = xq.shape
    g, r, n = luts.shape
    l = cfg.group_size
    assert r == (1 << l), (r, l)
    assert g * l >= k

    # Pad every axis to tile multiples (zero rows address LUT entry 0 == 0).
    pad_k = g * l - k
    if pad_k:
        xq = jnp.pad(xq, ((0, 0), (0, pad_k)))
    bm = min(bm, m)
    bn = min(bn, n)
    bg = _default_group_block(g, l) if bg is None else min(bg, g)
    pm, pn, pg = (-m) % bm, (-n) % bn, (-g) % bg
    if pm:
        xq = jnp.pad(xq, ((0, pm), (0, 0)))
    if pg:
        xq = jnp.pad(xq, ((0, 0), (0, pg * l)))
        luts = jnp.pad(luts, ((0, pg), (0, 0), (0, 0)))
    if pn:
        luts = jnp.pad(luts, ((0, 0), (0, 0), (0, pn)))
    mm, nn, gg = m + pm, n + pn, g + pg
    lut2d = luts.reshape(gg * r, nn)

    out = pl.pallas_call(
        functools.partial(_da_vmm_kernel, cfg=cfg),
        grid=(mm // bm, nn // bn, gg // bg),
        in_specs=[
            pl.BlockSpec((bm, bg * l), lambda i, j, gi: (i, gi)),
            # constant block index: fetched once, resident across the grid
            pl.BlockSpec((bg * l, bg * r), lambda i, j, gi: (0, 0)),
            pl.BlockSpec((bg * r, bn), lambda i, j, gi: (gi, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, gi: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.int32),
        interpret=interpret,
    )(xq.astype(jnp.int32), _address_expansion(bg, l), lut2d)
    return out[:m, :n]

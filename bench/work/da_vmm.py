"""Logical work of one DA vector-matrix product y[M, N] = x[M, K] W[K, N].

Charged the same whatever backend runs it: 2·M·K·N integer operations, and
the bytes a single pass must move — the int8 codes (K·N), the float32
per-column scales (4·N), the activations in (M·K) and out (M·N) at the
compute dtype. ``bitplane_stacked`` does 8x the MXU work of this product
and is charged only for the product.
"""
from __future__ import annotations

from typing import Tuple


def work(m: int, k: int, n: int, act_bytes: int = 2) -> Tuple[float, float]:
    """(integer ops, bytes) of one M x K x N DA call."""
    ops = 2.0 * m * k * n
    nbytes = float(k * n + 4 * n + act_bytes * m * k + act_bytes * m * n)
    return ops, nbytes

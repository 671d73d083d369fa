"""Logical work of one paged-attention read for one layer.

A row with ``n`` query tokens at positions ``pos .. pos+n-1`` attends, for
query ``p``, over the ``p + 1`` keys before and at it: ``4·heads·hd·(p+1)``
flops (scores and values). It must read its live K and V pages once —
``pages_for(pos + n)`` pages of ``page_size·kv·hd`` elements each, for K and
for V — and its queries in and outputs out. Garbage pages walked beyond a
row's live context are not useful work and are not counted.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def row_work(n: int, pos: int, *, heads: int, kv: int, hd: int,
             page_size: int, kv_bytes: int = 2,
             act_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one row's read in one layer."""
    keys = n * pos + n * (n + 1) // 2          # sum of (p + 1) over the row
    flops = 4.0 * heads * hd * keys
    pages = -(-(pos + n) // page_size)
    nbytes = (2.0 * pages * page_size * kv * hd * kv_bytes
              + 2.0 * n * heads * hd * act_bytes)
    return flops, nbytes


def work(rows: Iterable[Tuple[int, int]], **shape) -> Tuple[float, float]:
    """(flops, bytes) of one call over rows [(n, pos), ...], one layer."""
    flops = nbytes = 0.0
    for n, pos in rows:
        f, b = row_work(n, pos, **shape)
        flops += f
        nbytes += b
    return flops, nbytes

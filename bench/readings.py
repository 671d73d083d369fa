"""What the per-layer readers read: one traced window, reduced.

A reader (``bench/layer_metrics/<metric>.py``) gets a :class:`RunView` and
returns a number, or None where the window holds nothing for it to read.
The logical work of the window is counted here from the engine's calls,
the products and reads the configuration's architecture names for each
(``bench/architectures/``), and the kernels' work functions
(``bench/work/``), the same whatever backend ran it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from bench import catalog, tracing
from bench.peaks import least_time


@dataclasses.dataclass
class RunView:
    model: Dict
    serving: Dict
    peaks: Dict[str, float]
    window: Tuple[float, float]
    ticks: List[Tuple[float, float]]
    calls: Optional[list] = None              # sut.Call records
    capture: Optional[tracing.Capture] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @functools.cached_property
    def arch(self):
        """The configuration's architecture module: the products and reads
        of each call."""
        return catalog.architecture(self.model["reference"])

    # -- logical work ------------------------------------------------------
    def da_least_seconds(self) -> float:
        """Roofline least time of every DA product of every call."""
        da = catalog.work("da_vmm")
        total = 0.0
        for c in self.calls or []:
            for m, k, n in self.arch.da_products(self.model, c):
                ops, nb = da.work(m, k, n)
                total += least_time(ops, nb, self.peaks["int8_ops"],
                                    self.peaks["hbm_bytes_per_s"])
        return total

    def attn_least_seconds(self) -> float:
        """Roofline least time of every paged-attention read of every call
        (the architecture sums a call's reads over its layers)."""
        total = 0.0
        for c in self.calls or []:
            if c.rows:
                fl, nb = self.arch.attn_reads(self.model, self.serving, c)
                total += least_time(fl, nb, self.peaks["bf16_flops"],
                                    self.peaks["hbm_bytes_per_s"])
        return total

    def logical_ops(self) -> Tuple[float, float]:
        """(linear int ops, attention flops) the window's tokens need."""
        da = catalog.work("da_vmm")
        lin = att = 0.0
        for c in self.calls or []:
            lin += sum(da.work(m, k, n)[0]
                       for m, k, n in self.arch.da_products(self.model, c))
            att += self.arch.attn_reads(self.model, self.serving, c)[0]
        return lin, att

    # -- trace ---------------------------------------------------------------
    def device_seconds(self, pred) -> float:
        return tracing.scoped_seconds(self.capture, pred)

    def busy_seconds(self) -> float:
        return tracing.busy_seconds(self.capture.ops, self.capture.window)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(win, setup_s: float) -> Dict[str, float]:
    """The user-visible numbers of a window (drive.Window)."""
    ws, we = win.start, win.end
    out = {"setup_s": setup_s}
    toks = sum(1 for s in win.served.values() for t in s.req.token_times
               if ws < t <= we)
    out["out_tokens_per_s"] = toks / (we - ws)
    if any(s.due is not None for s in win.served.values()):
        ttft, itl = [], []
        for u in win.attempted:
            r = win.served[u].req
            first = r.first_token_t
            wait_end = first if first is not None else win.end + 60.0
            ttft.append(wait_end - win.served[u].due)
        for s in win.served.values():
            tt = s.req.token_times
            itl.extend(b - a for a, b in zip(tt, tt[1:]) if ws <= b < we)
        if ttft:
            out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        if itl:
            out["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    return out

"""Traffic generator: one general generator, driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the prompt- and output-length
distributions, the arrival process and a block size. Requests are made in
blocks: every block holds the same stratified sample of each distribution
(the quantiles at (i + 0.5) / block), and the seed only shuffles each block
and draws the token ids. So every seed offers the same work, in another
order, and any run that consumes whole blocks sees the same mix.

Arrivals:
  ``backlog``  no schedule; the harness keeps ``depth_per_lane x batch``
               requests queued.
  ``poisson``  an open loop at ``rate_per_s``: inter-arrival gaps are the
               exponential quantiles of each block, shuffled.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the traffic offers it."""

    index: int
    prompt_len: int
    output_len: int
    arrival_s: Optional[float]  # seconds after the schedule's start; None
    #                             for backlog traffic


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The n stratified lengths of a length distribution, clipped."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", int(v.max()) + 1)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def scale_lengths(mix: Dict, factor: float) -> Dict:
    """A copy of ``mix`` with every length bound scaled (CPU rehearsal)."""
    out = dict(mix)
    for key in ("prompt_len", "output_len"):
        d = dict(mix[key])
        for k in ("median", "min", "max", "value"):
            if k in d:
                d[k] = max(1, int(math.floor(d[k] * factor)))
        out[key] = d
    return out


class Traffic:
    """The request sequence of one mix and one seed, made block by block."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.block = int(mix.get("block", 64))
        self.prompts = quantiles(mix["prompt_len"], self.block)
        self.outputs = quantiles(mix["output_len"], self.block)
        arr = mix["arrival"]
        self.kind = arr["kind"]
        if self.kind == "poisson":
            rate = float(arr["rate_per_s"])
            u = (np.arange(self.block) + 0.5) / self.block
            self.gaps = -np.log1p(-u) / rate
        elif self.kind != "backlog":
            raise ValueError(f"unknown arrival process {self.kind!r}")
        self._specs: List[Spec] = []
        self._t = 0.0

    def _extend(self) -> None:
        b = len(self._specs) // self.block
        rng = _rng(self.seed, 1, b)
        p = rng.permutation(self.prompts)
        o = rng.permutation(self.outputs)
        g = rng.permutation(self.gaps) if self.kind == "poisson" else None
        for i in range(self.block):
            arrival = None
            if g is not None:
                self._t += float(g[i])
                arrival = self._t
            self._specs.append(Spec(len(self._specs), int(p[i]), int(o[i]),
                                    arrival))

    def spec(self, i: int) -> Spec:
        while len(self._specs) <= i:
            self._extend()
        return self._specs[i]

    def prompt(self, i: int) -> np.ndarray:
        """Token ids of request i's prompt, uniform over the vocabulary."""
        return _rng(self.seed, 2, i).integers(
            0, self.vocab, self.spec(i).prompt_len).astype(np.int32)

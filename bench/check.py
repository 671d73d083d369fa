"""``correct``: the served tokens against the plain float32 reference.

Once the window has closed and the engine is freed, a sample of the
requests the window finished — the longest of them and others drawn from
the seed, some hundreds of served tokens in all — is run through the
configuration's reference (``bench/references/<name>.py``) over prompt ++
served tokens. Each served token is greedy, so the reference's logit for
it should sit at the reference's best; the number compared is the widest
gap, over every sampled token, by which it lies below that best (+inf for
an id outside the vocabulary). The limit per cell is in
``bench/limits/<cell>.json`` with the readings it was set from.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Tuple

import numpy as np

from bench import catalog, weights

SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 16


def finished(win) -> List[int]:
    """uids the window finished: every token out by the window's close
    (backlog), or due in the window (poisson, after the drain)."""
    if any(s.due is not None for s in win.served.values()):
        pool = win.attempted
    else:
        pool = [u for u, s in win.served.items() if s.req.finish_t
                and s.req.finish_t <= win.end]
    return [u for u in pool
            if len(win.served[u].req.generated)
            >= win.served[u].req.max_new_tokens]


def bad_requests(win, vocab: int) -> int:
    """Requests of the window with a token out of range, or more tokens
    than asked for."""
    bad = 0
    for u in win.attempted:
        r = win.served[u].req
        g = np.asarray(r.generated, np.int64)
        if len(g) > r.max_new_tokens or (len(g) and (g.min() < 0
                                                     or g.max() >= vocab)):
            bad += 1
    return bad


def choose(win, uids: List[int], seed: int) -> List[int]:
    """The longest finished request, then others drawn from the seed until
    the sample holds SAMPLE_TOKENS served tokens."""
    if not uids:
        return []

    def size(u):
        r = win.served[u].req
        return len(r.prompt) + len(r.generated)

    longest = max(uids, key=lambda u: (size(u), u))
    rest = [u for u in sorted(uids) if u != longest]
    order = np.random.default_rng([int(seed) % (1 << 64), 7]).permutation(
        len(rest))
    out = [longest]
    tokens = len(win.served[longest].req.generated)
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        tokens += len(win.served[rest[i]].req.generated)
    return out


def reference_gaps(samples: List[Tuple[np.ndarray, List[int]]], model: Dict,
                   seed: int, pad_to: int) -> List[np.ndarray]:
    """Per sample (prompt, served tokens): the gap of every served token."""
    ref = catalog.reference(model["reference"])
    ends = weights.ends_f32(seed, model)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(g[:-1], np.int32)])
            for p, g in samples]
    hs = ref.hidden_states(seqs, model,
                           lambda li: weights.layer_f32(seed, model, li),
                           ends, pad_to)
    out = [_outside_vocab_is_inf(
               ref.served_gaps(h, len(p), g, ends["head"]), g,
               model["vocab_size"])
           for h, (p, g) in zip(hs, samples)]
    del ends, hs
    gc.collect()
    return out


def _outside_vocab_is_inf(gaps: np.ndarray, served: List[int],
                          vocab: int) -> np.ndarray:
    """A served id outside the vocabulary has no logit: its gap is +inf,
    not the NaN a lookup past the head's last column reads."""
    ids = np.asarray(served, np.int64)
    return np.where((ids < 0) | (ids >= vocab), np.inf, gaps)


def judge(gaps: List[np.ndarray], bad: int, failed: int,
          limit) -> Tuple[bool, Dict]:
    """correct, and each number compared beside its limit (a missing
    limit is never met)."""
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    widest = float(flat.max()) if flat.size else float("inf")
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "sampled_tokens": {"value": int(flat.size), "limit": 1},
        "bad_requests": {"value": bad, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    ok = (limit is not None and widest <= limit and flat.size >= 1
          and bad == 0 and failed == 0)
    return ok, checks

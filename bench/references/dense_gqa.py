"""Plain float32 reference of a dense GQA decoder (Qwen3, Mistral).

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching across requests beyond padding, nothing imported from the
program. The equations are those of the published models:

    x   = embed[tokens]
    per layer:
      h = rms_norm(x) * g_attn
      q, k, v = h Wq, h Wk, h Wv            (split into heads)
      q, k = rms_norm_per_head(q) * g_q, ... (Qwen3 only)
      q, k = rope(q), rope(k)               (rotate-half, theta^(-2i/hd))
      a = softmax(q k^T / sqrt(hd) + causal) v   (each KV head serves
                                                  heads/kv query heads)
      x = x + a Wo
      h = rms_norm(x) * g_ffn
      x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms_norm(x) * g_final) Whead

Weights come from :mod:`bench.weights` (the benchmark's own seeded draw),
one layer at a time, so the reference fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv          # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv", "hd", "eps",
                                             "theta", "qk_norm"))
def layer(x, w, *, heads, kv, hd, eps, theta, qk_norm):
    """One decoder layer over x [B, T, d] float32, causal from position 0."""
    b, t, _ = x.shape
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    h = _rms(x, w["norm_mixer"], eps)
    q = mm(h, w["wq"]).reshape(b, t, heads, hd)
    k = mm(h, w["wk"]).reshape(b, t, kv, hd)
    v = mm(h, w["wv"]).reshape(b, t, kv, hd)
    if qk_norm:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = heads // kv
    q = q.reshape(b, t, kv, g, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HIGHEST)
    x = x + mm(a.reshape(b, t, heads * hd), w["wo"])
    h = _rms(x, w["norm_ffn"], eps)
    f = jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"])
    return x + mm(f, w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def final_hidden(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def row_gaps(hrows, head, tokens):
    """Reference logits of rows hrows [R, d]: (max - logit[token]) per row,
    and the argmax. A served token that is the reference's best has gap 0."""
    logits = jnp.matmul(hrows, head, precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


def hidden_states(seqs: Sequence[np.ndarray], model: Dict,
                  layer_weights: Callable[[int], Dict], ends: Dict,
                  pad_to: int, group: int = 4) -> List[np.ndarray]:
    """Final-normed hidden states [len(seq), d] of each token sequence.

    Sequences are right-padded to ``pad_to`` (causal attention keeps pads
    out of every real position) and run ``group`` at a time; the layer loop
    is outermost, so each layer's weights are drawn once.
    """
    kw = dict(heads=model["num_attention_heads"],
              kv=model["num_key_value_heads"], hd=model["head_dim"],
              eps=float(model["rms_norm_eps"]),
              theta=float(model["rope_theta"]),
              qk_norm=bool(model.get("qk_norm", False)))
    n = -(-len(seqs) // group) * group  # whole groups: one compiled shape
    toks = np.zeros((n, pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s
    xs = [ends["embed"][jnp.asarray(toks[i: i + group])]
          for i in range(0, n, group)]
    for li in range(model["num_hidden_layers"]):
        w = layer_weights(li)
        xs = [layer(x, w, **kw) for x in xs]
        del w
    hs = [final_hidden(x, ends["final_norm"], eps=kw["eps"]) for x in xs]
    out = []
    for i, s in enumerate(seqs):
        out.append(np.asarray(hs[i // group][i % group, : len(s)]))
    return out


def served_gaps(hidden: np.ndarray, prompt_len: int, generated: Sequence[int],
                head, chunk: int = 256) -> np.ndarray:
    """Gap of every served token: token j was predicted at position
    prompt_len - 1 + j of the sequence prompt ++ generated."""
    rows = hidden[prompt_len - 1: prompt_len - 1 + len(generated)]
    toks = np.asarray(generated, np.int32)
    return chunked_gaps(rows, toks, head, chunk)


def chunked_gaps(rows: np.ndarray, toks: np.ndarray, head,
                 chunk: int = 256) -> np.ndarray:
    """row_gaps over rows [R, d] in fixed-size chunks (one compiled shape)."""
    out = []
    for i in range(0, len(toks), chunk):
        r, t = rows[i: i + chunk], toks[i: i + chunk]
        pad = chunk - len(t)
        r = np.pad(r, ((0, pad), (0, 0)))
        t = np.pad(t, (0, pad))
        gap, _ = row_gaps(jnp.asarray(r), head, jnp.asarray(t))
        out.append(np.asarray(gap)[: chunk - pad])
    return np.concatenate(out) if out else np.zeros((0,), np.float32)

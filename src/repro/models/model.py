"""LM assembly: scan-over-layers transformer / SSM / MoE / hybrid stacks.

Layers are grouped into *periods* (the layer-pattern repeat unit: 1 for
homogeneous stacks, 8 for jamba's attn:mamba 1:7 + MoE-every-2). Parameters of
each position within the period are stacked over ``n_periods`` and the model
scans over periods — one traced period regardless of depth, which keeps HLO
size and compile time flat for 80-layer models.

Modality stubs ([audio]/[vlm] per the assignment): the transformer backbone
accepts precomputed frame/patch embeddings [B, T, d_model] in place of token
ids; everything downstream is unchanged.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.sharding import constrain
from repro.models.attention import (
    KVCache,
    PagedKVCache,
    attention_forward,
    init_attention,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_embed,
    apply_lm_head,
    apply_mlp,
    apply_norm,
    init_embed,
    init_lm_head,
    init_mlp,
    init_norm,
)
from repro.models.mamba2 import MambaCache, init_mamba, mamba_forward
from repro.models.moe import init_moe, moe_forward

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# single block (mixer + ffn with pre-norms)
# ---------------------------------------------------------------------------
def init_block(key, cfg: ModelConfig, pos: int) -> Params:
    k1, k2 = jax.random.split(key)
    mixer = cfg.mixer_kind(pos)
    ffn = cfg.ffn_kind(pos)
    p: Params = {"norm_mixer": init_norm(cfg, cfg.d_model)}
    p["mixer"] = init_attention(k1, cfg) if mixer == "attn" else init_mamba(k1, cfg)
    if ffn != "none":
        p["norm_ffn"] = init_norm(cfg, cfg.d_model)
        p["ffn"] = (
            init_moe(k2, cfg) if ffn == "moe" else init_mlp(k2, cfg, cfg.d_model, cfg.d_ff)
        )
    return p


def block_forward(p, x, cfg: ModelConfig, pos: int, positions, cache,
                  update_cache, attn_bias=None, page_table=None, layer=None):
    mixer = cfg.mixer_kind(pos)
    ffn = cfg.ffn_kind(pos)
    h = apply_norm(p["norm_mixer"], x, cfg)
    if mixer == "attn":
        y, new_cache = attention_forward(
            p["mixer"], h, cfg, positions, cache, update_cache,
            attn_bias=attn_bias, page_table=page_table, layer=layer,
        )
    else:
        y, new_cache = mamba_forward(p["mixer"], h, cfg, cache, update_cache)
    x = x + y
    if ffn != "none":
        h = apply_norm(p["norm_ffn"], x, cfg)
        y = moe_forward(p["ffn"], h, cfg) if ffn == "moe" else apply_mlp(p["ffn"], h, cfg)
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------
def init_model(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 3)
    period = cfg.period
    layer_keys = jax.random.split(keys[0], cfg.n_periods * period).reshape(
        cfg.n_periods, period
    )
    periods = {}
    for pos in range(period):
        init_pos = functools.partial(init_block, cfg=cfg, pos=pos)
        periods[f"pos_{pos}"] = jax.vmap(lambda k: init_pos(k))(layer_keys[:, pos])
    params: Params = {
        "periods": periods,
        "final_norm": init_norm(cfg, cfg.d_model),
        "lm_head": init_lm_head(keys[1], cfg),
    }
    if cfg.modality == "text":
        params["embed"] = init_embed(keys[2], cfg)
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    """Decode caches stacked over periods: {pos_i: cache[n_periods, ...]}."""

    def stack(template):
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype), template
        )

    caches = {}
    for pos in range(cfg.period):
        if cfg.mixer_kind(pos) == "attn":
            caches[f"pos_{pos}"] = stack(KVCache.zeros(cfg, batch, max_len, dtype))
        else:
            caches[f"pos_{pos}"] = stack(MambaCache.zeros(cfg, batch, dtype))
    return caches


def forward(
    params: Params,
    inputs: jax.Array,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
    caches: Optional[Params] = None,
    update_cache: bool = False,
    last_logit_only: bool = False,
    page_table: Optional[jax.Array] = None,
    last_idx: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """inputs: tokens [B, T] int32, or embeddings [B, T, D] (modality stubs).

    last_logit_only: slice the final hidden state BEFORE the LM head —
    prefill needs one position's logits, not T×V (§Perf lever L2).

    page_table: int32 [B, W] physical-page ids when ``caches`` hold
    PagedKVCache pools (the serving runtime's paged layout); loop-invariant
    across the layer scan, like the hoisted causal bias.  How the paged
    read executes — the XLA gather or the fused Pallas page-walk kernel —
    is ``cfg.paged_attn``, resolved by platform in the engine's
    attention-backend registry (``core.engine.select_attn_backend``).

    PagedKVCache pools ride the layer scan's carry, whole and stacked over
    periods; the scan's only per-layer input for them is the period index,
    by which each layer writes its rows and reads its pages in place. As a
    scan input/output (``xs``/``ys``) each layer's pool would be sliced out
    and written back, two copies of the whole pool per call. Dense
    KVCache and Mamba caches stay scan inputs/outputs.

    last_idx: int32 [B] — per-row index of the last *real* token; the hidden
    state is gathered there before the LM head (the ragged-batch
    generalization of ``last_logit_only``, used by length-bucketed prefill
    and coalesced prefill+decode steps). Returns logits [B, 1, vocab].

    Returns (logits [B, T, vocab] or [B, 1, vocab], new_caches)."""
    if inputs.ndim == 2:
        h = apply_embed(params["embed"], inputs)
    else:
        h = constrain(inputs.astype(cfg.dtype()), ("batch", "seq", "embed"))
    b, t = h.shape[0], h.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    period = cfg.period
    have_cache = caches is not None

    # L8: hoist the [T, T] causal bias out of the layer scan (built once,
    # reused by every attention layer — see attention.causal_bias).
    attn_bias = None
    if cfg.attn_impl == "lean" and t > 1:
        from repro.models.attention import causal_bias

        attn_bias = causal_bias(t)

    # paged pools are loop-carried and indexed by layer; other caches scan
    paged = ({k: c for k, c in caches.items() if isinstance(c, PagedKVCache)}
             if have_cache else {})

    def period_fn(h, pools, period_params, period_caches, layer):
        pools = dict(pools)
        new_caches = {}
        for pos in range(period):
            key = f"pos_{pos}"
            if key in pools:
                h, pools[key] = block_forward(
                    period_params[key], h, cfg, pos, positions, pools[key],
                    update_cache, attn_bias=attn_bias, page_table=page_table,
                    layer=layer,
                )
                continue
            cache = period_caches[key] if have_cache else None
            h, nc = block_forward(
                period_params[key], h, cfg, pos, positions, cache,
                update_cache, attn_bias=attn_bias, page_table=page_table,
            )
            new_caches[key] = nc if nc is not None else 0
        return h, pools, new_caches

    if cfg.remat:
        policy = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.checkpoint_dots,
        }[cfg.remat_policy]
        period_fn = jax.checkpoint(period_fn, policy=policy)

    def scan_body(carry, xs):
        h, pools = carry
        period_params, period_caches, layer = xs
        h, pools, new_caches = period_fn(h, pools, period_params,
                                         period_caches, layer)
        return (h, pools), new_caches

    if have_cache:
        scanned = {k: c for k, c in caches.items() if k not in paged}
    else:
        scanned = {f"pos_{i}": jnp.zeros((cfg.n_periods,))
                   for i in range(period)}
    xs = (params["periods"], scanned,
          jnp.arange(cfg.n_periods, dtype=jnp.int32))
    (h, paged), new_caches = jax.lax.scan(
        scan_body, (h, paged), xs,
        unroll=cfg.n_periods if cfg.scan_unroll else 1,
    )
    if have_cache:
        new_caches = {k: paged[k] if k in paged else new_caches[k]
                      for k in caches}

    if last_idx is not None:
        h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)  # [B,1,D]
    elif last_logit_only:
        h = h[:, -1:]
    h = apply_norm(params["final_norm"], h, cfg)
    logits = apply_lm_head(params["lm_head"], h)
    return logits, (new_caches if have_cache else None)


# ---------------------------------------------------------------------------
# losses / parameter counting
# ---------------------------------------------------------------------------
def lm_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy in f32.

    Measured note (§Perf F1): a one-hot-einsum gold pick was tried on the
    hypothesis that take_along_axis would make GSPMD all-gather the
    vocab-sharded logits — refuted: the partitioner handles the gather
    locally, and the materialized [B,T,V] one-hot *added* ~8% to the memory
    term. take_along_axis stands."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def count_params(cfg: ModelConfig) -> int:
    import math

    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.key(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def count_active_params(cfg: ModelConfig) -> int:
    """Active params for MoE (6·N_active·D roofline): routed experts count
    top_k/n_experts of their weights."""
    total = count_params(cfg)
    if not cfg.n_experts:
        return total
    from repro.models.moe import padded_experts

    e_pad = padded_experts(cfg)
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    n_moe_layers = sum(
        1 for pos in range(cfg.period) if cfg.ffn_kind(pos) == "moe"
    ) * cfg.n_periods
    routed = n_moe_layers * e_pad * per_expert
    active = n_moe_layers * cfg.top_k * per_expert
    return total - routed + active

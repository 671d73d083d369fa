"""Seeded model weights, made by the benchmark and handed to the program.

The serving engine takes a float parameter tree and freezes it into DA
artifacts. The benchmark makes that tree itself, from ``--seed``, on the
device, in one jitted call and in the dtype it is served in (bfloat16), in
the layout ``ServeEngine`` reads. The plain reference regenerates the same
values one layer at a time (:func:`layer_f32`), so it takes nothing that the
program has made.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key, leaf),
layer)``, so one layer's leaves can be drawn alone and equal the stacked
draw bit for bit.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

#: leaf -> (id for fold_in, kind): kind "w" = N(0, 1/fan_in) matrix,
#: "s" = norm scale 1 + 0.1 N(0, 1)
LAYER_LEAVES = {
    "wq": (1, "w"), "wk": (2, "w"), "wv": (3, "w"), "wo": (4, "w"),
    "w_up": (5, "w"), "w_gate": (6, "w"), "w_down": (7, "w"),
    "norm_mixer": (8, "s"), "norm_ffn": (9, "s"),
    "q_norm": (10, "s"), "k_norm": (11, "s"),
}
EMBED_ID, HEAD_ID, FINAL_NORM_ID = 20, 21, 22


@functools.lru_cache(maxsize=None)
def _shape_table(d: int, heads: int, kv: int, hd: int, ff: int,
                 qk_norm: bool) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    qd, kvd = heads * hd, kv * hd
    shapes = {
        "wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
        "w_up": (d, ff), "w_gate": (d, ff), "w_down": (ff, d),
        "norm_mixer": (d,), "norm_ffn": (d,),
    }
    if qk_norm:
        shapes.update(q_norm=(hd,), k_norm=(hd,))
    return tuple(shapes.items())


def dims(model: Dict) -> Tuple[int, int, int, int, int, bool]:
    """(d_model, heads, kv_heads, head_dim, d_ff, qk_norm) of a config file."""
    return (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["intermediate_size"], bool(model.get("qk_norm", False)))


def _draw(key, ident: int, layer, shape, kind: str, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, ident), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "s":
        return (1.0 + 0.1 * z).astype(dtype)
    return (z * (1.0 / shape[0] ** 0.5)).astype(dtype)


def _layer(key, layer, table, dtype) -> Dict[str, jax.Array]:
    return {name: _draw(key, LAYER_LEAVES[name][0], layer, shape,
                        LAYER_LEAVES[name][1], dtype)
            for name, shape in table}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 2**64 - 1."""
    return jax.random.key(int(seed) % (1 << 64))


@functools.partial(jax.jit, static_argnames=("dims_", "n_layers", "vocab",
                                             "dtype"))
def _make(key, dims_, n_layers: int, vocab: int, dtype: str):
    d = dims_[0]
    table = _shape_table(*dims_)
    dt = jnp.dtype(dtype)
    stacked = jax.vmap(lambda l: _layer(key, l, table, dt))(
        jnp.arange(n_layers))
    block = {
        "norm_mixer": {"scale": stacked["norm_mixer"]},
        "mixer": {n: stacked[n] for n in ("wq", "wk", "wv", "wo", "q_norm",
                                          "k_norm") if n in stacked},
        "norm_ffn": {"scale": stacked["norm_ffn"]},
        "ffn": {n: stacked[n] for n in ("w_up", "w_gate", "w_down")},
    }
    return {
        "periods": {"pos_0": block},
        "final_norm": {"scale": _draw(key, FINAL_NORM_ID, 0, (d,), "s", dt)},
        "lm_head": {"w": _draw(key, HEAD_ID, 0, (d, vocab), "w", dt)},
        "embed": {"table": (0.02 * jax.random.normal(
            jax.random.fold_in(jax.random.fold_in(key, EMBED_ID), 0),
            (vocab, d), jnp.float32)).astype(dt)},
    }


def make_params(seed: int, model: Dict, dtype: str = "bfloat16"):
    """The whole served float tree, on the device, from one jitted call."""
    return _make(seed_key(seed), dims(model), model["num_hidden_layers"],
                 model["vocab_size"], dtype)


@functools.partial(jax.jit, static_argnames=("dims_", "dtype"))
def _layer_jit(key, layer, dims_, dtype):
    dt = jnp.dtype(dtype)
    served = _layer(key, layer, _shape_table(*dims_), dt)
    return {k: v.astype(jnp.float32) for k, v in served.items()}


def layer_f32(seed: int, model: Dict, layer: int, dtype: str = "bfloat16"):
    """Layer ``layer``'s leaves as served (rounded to ``dtype``), float32."""
    return _layer_jit(seed_key(seed), jnp.int32(layer), dims(model), dtype)


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def _ends_jit(key, d: int, vocab: int, dtype: str):
    dt = jnp.dtype(dtype)
    embed = (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, EMBED_ID), 0),
        (vocab, d), jnp.float32)).astype(dt)
    return {
        "embed": embed.astype(jnp.float32),
        "final_norm": _draw(key, FINAL_NORM_ID, 0, (d,), "s", dt
                            ).astype(jnp.float32),
        "head": _draw(key, HEAD_ID, 0, (d, vocab), "w", dt
                      ).astype(jnp.float32),
    }


def ends_f32(seed: int, model: Dict, dtype: str = "bfloat16"):
    """Embedding, final norm scale and LM head as served, in float32."""
    return _ends_jit(seed_key(seed), model["hidden_size"],
                     model["vocab_size"], dtype)

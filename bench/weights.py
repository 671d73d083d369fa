"""Seeded model weights, made by the benchmark and handed to the program.

The serving engine takes a float parameter tree and freezes it into DA
artifacts. The benchmark makes that tree itself, from ``--seed``, on the
device, in one jitted call and in the dtype it is served in (bfloat16). The
configuration's architecture (``bench/architectures/<name>.py``) says which
leaves each group of layers has and arranges the drawn arrays in the layout
``ServeEngine`` reads; the draw itself is the same for every architecture.
The plain reference regenerates the same values one layer at a time
(:func:`layer_f32`), so it takes nothing that the program has made.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key, leaf),
layer)`` with the layer's absolute index, so one layer's leaves can be
drawn alone and equal the stacked draw bit for bit. The ends (embedding,
final norm, LM head) are drawn here for every architecture.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import catalog

#: ids of the ends for fold_in; a layer's leaves take ids below them
EMBED_ID, HEAD_ID, FINAL_NORM_ID = 20, 21, 22


def _draw(key, ident: int, layer, shape, kind: str, dtype):
    """kind "w": N(0, 1/fan_in) over the second-to-last axis; kind "s": a
    norm scale 1 + 0.1 N(0, 1)."""
    k = jax.random.fold_in(jax.random.fold_in(key, ident), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "s":
        return (1.0 + 0.1 * z).astype(dtype)
    return (z * (1.0 / shape[-2] ** 0.5)).astype(dtype)


def _layer(key, layer, leaves, dtype) -> Dict[str, jax.Array]:
    return {name: _draw(key, ident, layer, shape, kind, dtype)
            for name, ident, kind, shape in leaves}


def _ends(key, d: int, vocab: int, dtype) -> Dict[str, jax.Array]:
    embed = 0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, EMBED_ID), 0),
        (vocab, d), jnp.float32)
    return {"embed": embed.astype(dtype),
            "final_norm": _draw(key, FINAL_NORM_ID, 0, (d,), "s", dtype),
            "head": _draw(key, HEAD_ID, 0, (d, vocab), "w", dtype)}


def groups(model: Dict) -> Tuple[Tuple, ...]:
    """The architecture's layer groups as hashable tuples: ((layer, ...),
    ((name, fold_in id, kind, shape), ...)) each."""
    arch = catalog.architecture(model["reference"])
    return tuple((tuple(int(l) for l in layers),
                  tuple((name, int(ident), kind, tuple(shape))
                        for name, ident, kind, shape in leaves))
                 for layers, leaves in arch.layer_groups(model))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 2**64 - 1."""
    return jax.random.key(int(seed) % (1 << 64))


@functools.partial(jax.jit, static_argnames=("groups_", "d", "vocab",
                                             "dtype"))
def _draw_all(key, groups_, d: int, vocab: int, dtype: str):
    """Every group's leaves, stacked over its layers, and the ends."""
    dt = jnp.dtype(dtype)
    stacks = [jax.vmap(lambda l, leaves=leaves: _layer(key, l, leaves, dt))(
        jnp.asarray(layers, jnp.int32)) for layers, leaves in groups_]
    return stacks, _ends(key, d, vocab, dt)


def make_params(seed: int, model: Dict, dtype: str = "bfloat16"):
    """The whole served float tree, on the device, from one jitted call;
    the architecture only arranges what that call drew."""
    stacks, ends = _draw_all(seed_key(seed), groups(model),
                             model["hidden_size"], model["vocab_size"],
                             dtype)
    arch = catalog.architecture(model["reference"])
    return arch.served_tree(stacks, ends, model)


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _layer_jit(key, layer, leaves, dtype):
    served = _layer(key, layer, leaves, jnp.dtype(dtype))
    return {k: v.astype(jnp.float32) for k, v in served.items()}


def layer_f32(seed: int, model: Dict, layer: int, dtype: str = "bfloat16"):
    """Layer ``layer``'s leaves as served (rounded to ``dtype``), float32,
    drawn with its group's leaf table."""
    leaves = next(lv for layers, lv in groups(model) if layer in layers)
    return _layer_jit(seed_key(seed), jnp.int32(layer), leaves, dtype)


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def _ends_jit(key, d: int, vocab: int, dtype: str):
    served = _ends(key, d, vocab, jnp.dtype(dtype))
    return {k: v.astype(jnp.float32) for k, v in served.items()}


def ends_f32(seed: int, model: Dict, dtype: str = "bfloat16"):
    """Embedding, final norm scale and LM head as served, in float32."""
    return _ends_jit(seed_key(seed), model["hidden_size"],
                     model["vocab_size"], dtype)

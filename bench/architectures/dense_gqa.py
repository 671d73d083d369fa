"""What the benchmark knows of a dense GQA decoder (Qwen3, Mistral).

Every fact of ``bench/`` that depends on the model's shape sits here, and
the generic code asks for it by the configuration's ``reference`` name
(``catalog.architecture``):

* ``model_config``: the program's ``ModelConfig`` of a configuration file;
* ``rehearsal``: the configuration and serving settings cut to a tiny size
  for the CPU rehearsal;
* ``layer_groups``: the layers that share one leaf table, with the leaves
  ``bench/weights.py`` draws for them;
* ``served_tree``: the drawn arrays in the layout ``ServeEngine`` reads;
* ``da_products``, ``attn_reads``: the logical work of one engine call,
  which ``bench/readings.py`` charges against the chip's peaks.

A dense GQA layer is an attention block (fused QKV, O) and a SwiGLU FFN
(up, gate, down), every layer alike.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.work import paged_attn

#: a reduced model for the CPU rehearsal: same family, tiny widths
REHEARSAL_MODEL = {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 97}
REHEARSAL_SERVING = {"batch": 4, "max_len": 64, "page_size": 8}

#: mixer leaves and FFN leaves of one layer, in the engine's tree
MIXER = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
FFN = ("w_up", "w_gate", "w_down")


def model_config(model: Dict):
    """The program's ModelConfig for a configuration file; keys the file
    does not set keep the program's defaults."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=model["name"], family="dense",
        n_layers=int(model["num_hidden_layers"]),
        d_model=int(model["hidden_size"]), vocab=int(model["vocab_size"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        qk_norm=bool(model.get("qk_norm", False)),
        rope_theta=float(model["rope_theta"]),
        d_ff=int(model["intermediate_size"]), mlp_act="swiglu",
        norm_eps=float(model["rms_norm_eps"]),
        param_dtype=model.get("torch_dtype", "bfloat16"),
        compute_dtype=model.get("torch_dtype", "bfloat16"),
    )


def rehearsal(model: Dict, serving: Dict) -> Tuple[Dict, Dict]:
    return {**model, **REHEARSAL_MODEL}, {**serving, **REHEARSAL_SERVING}


def layer_leaves(model: Dict, d_ff: int) -> Tuple[Tuple, ...]:
    """(name, fold_in id, kind, shape) of one layer's leaves at FFN width
    ``d_ff``: kind "w" is a matrix, "s" a norm scale."""
    d, hd = model["hidden_size"], model["head_dim"]
    qd = model["num_attention_heads"] * hd
    kvd = model["num_key_value_heads"] * hd
    leaves = [("wq", 1, "w", (d, qd)), ("wk", 2, "w", (d, kvd)),
              ("wv", 3, "w", (d, kvd)), ("wo", 4, "w", (qd, d)),
              ("w_up", 5, "w", (d, d_ff)), ("w_gate", 6, "w", (d, d_ff)),
              ("w_down", 7, "w", (d_ff, d)),
              ("norm_mixer", 8, "s", (d,)), ("norm_ffn", 9, "s", (d,))]
    if model.get("qk_norm", False):
        leaves += [("q_norm", 10, "s", (hd,)), ("k_norm", 11, "s", (hd,))]
    return tuple(leaves)


def layer_groups(model: Dict) -> Tuple[Tuple, ...]:
    """One group: every layer, one leaf table."""
    layers = tuple(range(model["num_hidden_layers"]))
    return ((layers, layer_leaves(model, model["intermediate_size"])),)


def block(stacked: Dict) -> Dict:
    """One stack of layers' leaves in the engine's block layout."""
    return {
        "norm_mixer": {"scale": stacked["norm_mixer"]},
        "mixer": {n: stacked[n] for n in MIXER if n in stacked},
        "norm_ffn": {"scale": stacked["norm_ffn"]},
        "ffn": {n: stacked[n] for n in FFN},
    }


def served_tree(groups: List[Dict], ends: Dict, model: Dict) -> Dict:
    """The float tree ``ServeEngine`` freezes: the one group's stacked
    leaves as the model's single period, and the ends."""
    (stacked,) = groups
    return {
        "periods": {"pos_0": block(stacked)},
        "final_norm": {"scale": ends["final_norm"]},
        "lm_head": {"w": ends["head"]},
        "embed": {"table": ends["embed"]},
    }


def layer_products(model: Dict, d_ff: int) -> List[Tuple[int, int]]:
    """(K, N) of each DA product of one layer at FFN width ``d_ff``: fused
    QKV, O, up, gate, down."""
    d = model["hidden_size"]
    qd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    return [(d, qd + 2 * kvd), (qd, d), (d, d_ff), (d, d_ff), (d_ff, d)]


def head_product(model: Dict, call) -> List[Tuple[int, int, int]]:
    """The LM head over the rows whose logits became a token."""
    if not call.head_rows:
        return []
    return [(call.head_rows, model["hidden_size"], model["vocab_size"])]


def da_products(model: Dict, call) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every DA product of one call: each layer's five at M =
    the call's tokens, then the LM head."""
    m = sum(n for n, _ in call.rows)
    out = []
    if m:
        per_layer = layer_products(model, model["intermediate_size"])
        for _ in range(model["num_hidden_layers"]):
            out += [(m, k, n) for k, n in per_layer]
    return out + head_product(model, call)


def attn_shape(model: Dict, serving: Dict) -> Dict[str, int]:
    return dict(heads=model["num_attention_heads"],
                kv=model["num_key_value_heads"], hd=model["head_dim"],
                page_size=int(serving["page_size"]))


def attn_reads(model: Dict, serving: Dict, call) -> Tuple[float, float]:
    """(flops, bytes) of the call's paged-attention reads: one read per
    layer, every layer alike."""
    flops, nbytes = paged_attn.work(call.rows,
                                    **attn_shape(model, serving))
    layers = model["num_hidden_layers"]
    return layers * flops, layers * nbytes

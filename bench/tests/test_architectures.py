"""The architecture module (``bench/architectures/``) holds every fact of
a model's shape, and the generic harness asks it by name.

* Moving the dense GQA facts into ``bench/architectures/dense_gqa.py``
  changed no number: the weights are bit-identical and the work totals
  equal. WEIGHTS and WORK below were recorded at the parent commit, before
  the move, by the same calls on the CPU (``make_params``, ``layer_f32``
  and ``ends_f32`` at the tiny sizes of ``test_reference.py``, seed
  2**31 + 17; ``RunView``'s three work totals at the published widths, on
  a "TPU v5 lite"'s peaks). The ModelConfig and rehearsal sizes are the
  parent's too.
* A second architecture comes in as files alone: a throwaway module and a
  configuration that names it, in a copy of ``bench/`` that ``catalog``
  is pointed at, with no ``bench/*.py`` edited.
"""
import hashlib
import shutil

import jax
import numpy as np
import pytest

from bench import catalog, readings, sut, weights
from bench.peaks import peaks_for
from bench.sut import Call
from bench.tests.test_reference import TINY_OF

SEED = 2**31 + 17
CALLS = {
    "decode32": Call("decode", 0.0, 0.01,
                     [(1, 100 + 7 * i) for i in range(32)], 32, (32, 1)),
    "prefill4x16": Call("prefill", 0.0, 0.01,
                        [(16, 0), (16, 128), (16, 512), (16, 896)], 1,
                        (4, 16)),
    "decode1at900": Call("decode", 0.0, 0.01, [(1, 900)], 1, (1, 1)),
}
#: leaf -> sha256 (first 16 hex digits) of its bytes, dtype and shape
WEIGHTS = {
    "qwen3-8b": {
        "make_params": {
            "embed.table": "d860785cbe9b9292",
            "final_norm.scale": "faa5748295d84704",
            "lm_head.w": "bd35fa2aee8d937f",
            "periods.pos_0.ffn.w_down": "02e4ddd2db279afd",
            "periods.pos_0.ffn.w_gate": "bee2c9ebb107f2eb",
            "periods.pos_0.ffn.w_up": "dce23626fa26a9fd",
            "periods.pos_0.mixer.k_norm": "5e43ae5d073f59d1",
            "periods.pos_0.mixer.q_norm": "9af512bc0e80afdd",
            "periods.pos_0.mixer.wk": "3c20222038ea3670",
            "periods.pos_0.mixer.wo": "d90630f817ad259f",
            "periods.pos_0.mixer.wq": "f6fba62053df6aa7",
            "periods.pos_0.mixer.wv": "8ede4a690aead63b",
            "periods.pos_0.norm_ffn.scale": "691b7131988f341c",
            "periods.pos_0.norm_mixer.scale": "883129c365509a43",
        },
        "ends_f32": {
            "embed": "c4fdb04cfa9fad0a",
            "final_norm": "494b523c485b0b45",
            "head": "63869d2f49761787",
        },
        "layer_f32[0]": {
            "k_norm": "2bbed770ab5afc30",
            "norm_ffn": "6e638dbc81e6bbf9",
            "norm_mixer": "9a58be9f2360260d",
            "q_norm": "e3d1b887dd2e33bd",
            "w_down": "59ba180ecd3f687f",
            "w_gate": "c9ea1c53c67cb910",
            "w_up": "27cee2a7184b1f88",
            "wk": "00f69e5c1f9ee138",
            "wo": "2d644d976ae7087c",
            "wq": "72415f3cd5dd0e5b",
            "wv": "114f997956560639",
        },
        "layer_f32[1]": {
            "k_norm": "8663c28c16784c41",
            "norm_ffn": "f4cddcc7c7086afa",
            "norm_mixer": "f4c99d20ce3c5436",
            "q_norm": "4e2bb082f9784909",
            "w_down": "16ac5784a1dc9413",
            "w_gate": "46944bdac4b19c4c",
            "w_up": "7aeb1a1ad6beb56e",
            "wk": "fa1a031c074ded93",
            "wo": "284041be77da6b5c",
            "wq": "228737ce2e7c6526",
            "wv": "a62be9d9a2bb5428",
        },
    },
    "mistral-nemo-12b": {
        "make_params": {
            "embed.table": "d860785cbe9b9292",
            "final_norm.scale": "faa5748295d84704",
            "lm_head.w": "bd35fa2aee8d937f",
            "periods.pos_0.ffn.w_down": "02e4ddd2db279afd",
            "periods.pos_0.ffn.w_gate": "bee2c9ebb107f2eb",
            "periods.pos_0.ffn.w_up": "dce23626fa26a9fd",
            "periods.pos_0.mixer.wk": "5985d7c1bddce860",
            "periods.pos_0.mixer.wo": "262512519dbc440f",
            "periods.pos_0.mixer.wq": "3661f76ef0b8716e",
            "periods.pos_0.mixer.wv": "4dd0a496b4915128",
            "periods.pos_0.norm_ffn.scale": "691b7131988f341c",
            "periods.pos_0.norm_mixer.scale": "883129c365509a43",
        },
        "ends_f32": {
            "embed": "c4fdb04cfa9fad0a",
            "final_norm": "494b523c485b0b45",
            "head": "63869d2f49761787",
        },
        "layer_f32[0]": {
            "norm_ffn": "6e638dbc81e6bbf9",
            "norm_mixer": "9a58be9f2360260d",
            "w_down": "59ba180ecd3f687f",
            "w_gate": "c9ea1c53c67cb910",
            "w_up": "27cee2a7184b1f88",
            "wk": "93dc857b1d9f4383",
            "wo": "5332822a311ec026",
            "wq": "044a720008d91d61",
            "wv": "af8dd5998cfad765",
        },
        "layer_f32[1]": {
            "norm_ffn": "f4cddcc7c7086afa",
            "norm_mixer": "f4c99d20ce3c5436",
            "w_down": "16ac5784a1dc9413",
            "w_gate": "46944bdac4b19c4c",
            "w_up": "7aeb1a1ad6beb56e",
            "wk": "de327c2eaa19bfd5",
            "wo": "dc13ee140a0f24c7",
            "wq": "65e7c7ba1651a88e",
            "wv": "bb1097239bbc63a0",
        },
    },
}
#: (da_least_seconds, attn_least_seconds, *logical_ops) of each call
WORK = {
    "qwen3-8b": {
        "decode32": (0.0027011913455433456, 0.00028294907936507935,
                     138613358592.0, 878706688.0),
        "prefill4x16": (0.002731629714285714, 7.425812942612942e-05,
                        198813155328.0, 3292528640.0),
        "decode1at900": (0.0026484494065934067, 3.66489474969475e-05,
                         4331667456.0, 118095872.0),
    },
    "mistral-nemo-12b": {
        "decode32": (0.0025269969426129427, 0.0001768431746031746,
                     130191196160.0, 549191680.0),
        "prefill4x16": (0.0025474944468864467, 4.641133089133089e-05,
                        175825223680.0, 2057830400.0),
        "decode1at900": (0.0024868421391941392, 2.2905592185592187e-05,
                         4068474880.0, 73809920.0),
    },
}

#: what the parent's ``sut.model_config`` set; every other field keeps the
#: program's default
MODEL_CONFIG = {
    "qwen3-8b": dict(name="qwen3-8b", family="dense", n_layers=8,
                     d_model=4096, vocab=151936, n_heads=32, n_kv_heads=8,
                     head_dim=128, qk_norm=True, rope_theta=1e6, d_ff=12288,
                     mlp_act="swiglu", norm_eps=1e-6),
    "mistral-nemo-12b": dict(name="mistral-nemo-12b", family="dense",
                             n_layers=5, d_model=5120, vocab=131072,
                             n_heads=32, n_kv_heads=8, head_dim=128,
                             qk_norm=False, rope_theta=1e6, d_ff=14336,
                             mlp_act="swiglu", norm_eps=1e-5),
}
REHEARSAL_MODEL = {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 97}
REHEARSAL_SERVING = {"batch": 4, "max_len": 64, "page_size": 8}


def _hashes(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        name = ".".join(str(getattr(p, "key", p)) for p in path)
        out[name] = hashlib.sha256(
            a.tobytes() + str((a.dtype, a.shape)).encode()).hexdigest()[:16]
    return out


def _view(model, calls):
    return readings.RunView(model, model["serving"],
                            peaks_for("TPU v5 lite"), (0.0, 1.0), [], calls)


@pytest.mark.parametrize("config", sorted(TINY_OF))
def test_weights_are_bit_identical_to_the_parents(config):
    model = {**catalog.config(config), **TINY_OF[config]}
    want = WEIGHTS[config]
    assert _hashes(weights.make_params(SEED, model)) == want["make_params"]
    assert _hashes(weights.ends_f32(SEED, model)) == want["ends_f32"]
    for layer in range(model["num_hidden_layers"]):
        assert (_hashes(weights.layer_f32(SEED, model, layer))
                == want[f"layer_f32[{layer}]"])


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("config", sorted(TINY_OF))
def test_work_totals_equal_the_parents(config, call):
    view = _view(catalog.config(config), [CALLS[call]])
    got = (view.da_least_seconds(), view.attn_least_seconds(),
           *view.logical_ops())
    assert got == pytest.approx(WORK[config][call], rel=1e-12, abs=0)


@pytest.mark.parametrize("config", sorted(TINY_OF))
def test_model_config_and_rehearsal_are_the_parents(config):
    from repro.models.config import ModelConfig

    model = catalog.config(config)
    assert sut.model_config(model) == ModelConfig(**MODEL_CONFIG[config])
    serving = model["serving"]
    assert sut.rehearsal_model(model, serving) == (
        {**model, **REHEARSAL_MODEL}, {**serving, **REHEARSAL_SERVING})


#: a throwaway architecture: layer 0 is dense GQA with twice the FFN
#: width, layers 1.. are dense GQA as configured
WIDE_FIRST = '''
from bench import catalog

DENSE = catalog.architecture("dense_gqa")


def layer_groups(model):
    rest = tuple(range(1, model["num_hidden_layers"]))
    return (((0,), DENSE.layer_leaves(model, 2 * model["intermediate_size"])),
            (rest, DENSE.layer_leaves(model, model["intermediate_size"])))


def served_tree(groups, ends, model):
    first, rest = groups
    return {"first": DENSE.block(first), "rest": DENSE.block(rest),
            "ends": dict(ends)}


def da_products(model, call):
    m = sum(n for n, _ in call.rows)
    ff = model["intermediate_size"]
    per = [DENSE.layer_products(model, 2 * ff)]
    per += [DENSE.layer_products(model, ff)] * (model["num_hidden_layers"] - 1)
    out = [(m, k, n) for layer in per for k, n in layer] if m else []
    return out + DENSE.head_product(model, call)


def attn_reads(model, serving, call):
    return DENSE.attn_reads(model, serving, call)
'''


def test_a_second_architecture_is_added_as_files_alone(tmp_path,
                                                       monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(catalog.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "architectures" / "wide_first.py").write_text(WIDE_FIRST)
    (bench / "configs" / "wide-first.json").write_text(
        (bench / "configs" / "qwen3-8b.json").read_text().replace(
            '"reference": "dense_gqa"', '"reference": "wide_first"'))
    monkeypatch.setattr(catalog, "BENCH", bench)
    model = {**catalog.config("wide-first"), **TINY_OF["qwen3-8b"],
             "num_hidden_layers": 3}
    assert model["reference"] == "wide_first"

    draws = []
    real = weights._draw_all

    def counted(*a, **kw):
        draws.append(real(*a, **kw))
        return draws[-1]

    monkeypatch.setattr(weights, "_draw_all", counted)
    tree = weights.make_params(SEED, model)
    assert len(draws) == 1  # both groups and the ends, one jitted call
    drawn = {id(a) for a in jax.tree_util.tree_leaves(draws[0])}
    assert all(id(a) in drawn for a in jax.tree_util.tree_leaves(tree))
    d, ff = model["hidden_size"], model["intermediate_size"]
    assert tree["first"]["ffn"]["w_up"].shape == (1, d, 2 * ff)
    assert tree["rest"]["ffn"]["w_up"].shape == (2, d, ff)

    for layer in range(3):
        block, i = (tree["first"], 0) if layer == 0 else (tree["rest"],
                                                          layer - 1)
        stacked = {"norm_mixer": block["norm_mixer"]["scale"],
                   "norm_ffn": block["norm_ffn"]["scale"],
                   **block["mixer"], **block["ffn"]}
        alone = weights.layer_f32(SEED, model, layer)
        assert sorted(alone) == sorted(stacked)
        for name, a in alone.items():
            np.testing.assert_array_equal(
                np.asarray(a),
                np.asarray(stacked[name][i]).astype(np.float32))

    call = CALLS["prefill4x16"]
    dense = catalog.architecture("dense_gqa")
    da = catalog.work("da_vmm")
    products = [(64, k, n) for k, n in dense.layer_products(model, 2 * ff)]
    products += 2 * [(64, k, n) for k, n in dense.layer_products(model, ff)]
    products += [(1, d, model["vocab_size"])]
    view = _view(model, [call])
    peaks = view.peaks
    want = sum(max(da.work(*p)[0] / peaks["int8_ops"],
                   da.work(*p)[1] / peaks["hbm_bytes_per_s"])
               for p in products)
    assert view.da_least_seconds() == pytest.approx(want, rel=1e-12)
    assert view.logical_ops()[0] == sum(da.work(*p)[0] for p in products)

"""mistral-nemo-12b: attention narrower than the model.

Its query width (32 heads x 128 = 4096) is below its model width (5120), so
``wq`` is 5120 -> 4096, ``wo`` 4096 -> 5120 and the fused QKV product has
K 5120, N 6144. These tests hold the program to the benchmark's plain
reference (``bench/references/dense_gqa.py``) at a tiny size that keeps
that shape (d_model 80 against 4 heads of 16): the float forward, and a
frozen DA artifact served through the paged runtime, prefill in chunks
across page boundaries and then decode, with both paged reads.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:  # the benchmark's reference and weights
    sys.path.insert(0, str(REPO))

from bench import catalog, sut, weights  # noqa: E402
from repro.configs.registry import ARCHS  # noqa: E402
from repro.core.engine import x_bits_override  # noqa: E402
from repro.models.model import forward, init_model  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.scheduler import make_paged_logits  # noqa: E402

CONFIG = "mistral-nemo-12b"
TINY = dict(hidden_size=80, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=101)
SEED = 2**33 + 5
PAGE, CHUNK, MAX_LEN = 8, 16, 64
#: two prompts: chunks 16 + 5 and 16 + 11, over the page boundaries at 8,
#: 16 and 24; then decode across the boundaries at 24 and 32
PROMPTS = (21, 27)
MAX_NEW = 10


def _tiny():
    model = {**catalog.config(CONFIG), **TINY}
    cfg = dataclasses.replace(sut.model_config(model),
                              param_dtype="float32", compute_dtype="float32")
    return model, cfg


def _reference_logits(model, seqs):
    """The reference's logits at every position of each sequence."""
    ref = catalog.reference(model["reference"])
    ends = weights.ends_f32(SEED, model, dtype="float32")
    hs = ref.hidden_states(
        [np.asarray(s, np.int32) for s in seqs], model,
        lambda li: weights.layer_f32(SEED, model, li, dtype="float32"),
        ends, pad_to=MAX_LEN, group=2)
    head = np.asarray(ends["head"])
    return [h @ head for h in hs]


def test_zoo_entry_and_benchmark_config_agree():
    zoo = ARCHS[CONFIG]
    f = catalog.config(CONFIG)
    assert (zoo.d_model, zoo.d_ff, zoo.n_heads, zoo.n_kv_heads, zoo.head_dim,
            zoo.vocab) == (f["hidden_size"], f["intermediate_size"],
                           f["num_attention_heads"],
                           f["num_key_value_heads"], f["head_dim"],
                           f["vocab_size"]) == (5120, 14336, 32, 8, 128,
                                                131072)
    assert zoo.n_layers == f["reduced"]["num_hidden_layers"]["published"] \
        == 40
    assert zoo.norm_eps == f["rms_norm_eps"] == 1e-5
    assert zoo.rope_theta == f["rope_theta"] == 1e6
    assert zoo.qk_norm is f["qk_norm"] is False
    assert zoo.tie_embeddings is f["tie_word_embeddings"] is False
    assert f["reference"] == "dense_gqa"
    cfg = sut.model_config(f)
    assert (cfg.q_dim, cfg.kv_dim, cfg.d_model) == (4096, 1024, 5120)


def test_reference_matches_program_float_forward():
    """The program lays its parameters out as the benchmark draws them
    (``wq`` 80 -> 64, ``wo`` 64 -> 80), and at "highest" precision its float
    forward and the plain reference, written independently, agree to
    float32 rounding (the 2e-4 of bench/tests/test_reference.py)."""
    model, cfg = _tiny()
    assert cfg.q_dim == 64 != cfg.d_model == 80
    params = weights.make_params(SEED, model, dtype="float32")
    own = jax.eval_shape(lambda: init_model(jax.random.key(0), cfg))
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)
    assert params["periods"]["pos_0"]["mixer"]["wo"].shape == (2, 64, 80)
    toks = np.random.default_rng(0).integers(0, 101, (2, 24)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, jnp.asarray(toks), cfg)
    got = np.stack(_reference_logits(model, list(toks)))
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)


def _serve(paged_attn, x_bits=None):
    """Serve both prompts through the paged runtime from a frozen DA
    artifact, keeping the logits of every row the step computed (the step
    returns token ids, so the spy reads the same call's logits through
    ``make_paged_logits`` first): [(uid, position, logits)], and the
    sequences prompt ++ served tokens."""
    model, cfg = _tiny()
    params = weights.make_params(SEED, model, dtype="float32")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 101, n).astype(np.int32) for n in PROMPTS]
    rows = []
    with x_bits_override(x_bits):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=MAX_LEN,
                          da_mode="bitplane_stacked", runtime="paged",
                          page_size=PAGE, prefill_chunk=CHUNK,
                          paged_attn=paged_attn)
        step = eng._rt._step
        logits_of = jax.jit(make_paged_logits(eng._rt.cfg))

        def spy(params, caches, tokens, positions, table, last_idx):
            logits, _ = logits_of(params, caches, tokens, positions, table,
                                  last_idx)
            ids, caches = step(params, caches, tokens, positions, table,
                               last_idx)
            # the served ids are the argmax of the logits kept for the check
            np.testing.assert_array_equal(np.asarray(ids),
                                          np.argmax(np.asarray(logits), -1))
            rows.append(tuple(np.asarray(a) for a in (tokens, positions,
                                                      last_idx, logits)))
            return ids, caches

        eng._rt._step = spy
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=MAX_NEW))
        done = eng.run()
    seqs = [np.concatenate([prompts[u], done[u].generated]) for u in (0, 1)]
    out = []
    for tokens, positions, last_idx, logits in rows:
        for r in range(tokens.shape[0]):
            n = int(last_idx[r]) + 1
            pos, tok = positions[r, :n], tokens[r, :n]
            owners = [u for u, s in enumerate(seqs)
                      if pos.max() < len(s) and np.all(s[pos] == tok)]
            if not owners:
                continue  # a pad row
            assert len(owners) == 1
            out.append((owners[0], int(pos[-1]), logits[r]))
    return seqs, out


def _gaps(paged_attn, x_bits=None):
    """Largest |served logit - reference logit| over every row the engine
    computed, in units of the reference logits' spread at that row."""
    model, _ = _tiny()
    seqs, rows = _serve(paged_attn, x_bits)
    ref = _reference_logits(model, seqs)
    positions = {u: sorted(p for v, p, _ in rows if v == u) for u in (0, 1)}
    # every chunk end of the prompt, then every decoded position
    for u, n in enumerate(PROMPTS):
        assert positions[u] == [CHUNK - 1] + list(range(n - 1,
                                                        n + MAX_NEW - 1))
    return max(float(np.max(np.abs(lg - ref[u][p])) / np.std(ref[u][p]))
               for u, p, lg in rows)


#: The DA artifact quantizes each product's activations to 8 bits (one
#: scale per row) and its weights to int8 codes (one scale per column), so
#: every product carries a relative error near 2^-7 of its operands' range.
#: Through 2 layers of 5 products and the head, the served logits sit at most
#: 0.128 of the float32 reference's spread from it, on both reads (bitwise
#: the same). 0.2 leaves that rounding 1.5x room, and none for a product of
#: the wrong shape or layout, nor for fewer activation bit-planes: 7 read
#: 0.298, 6 read 0.781 and 4 read 2.887.
LOGIT_TOL = 0.2


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_served_logits_match_reference(paged_attn):
    assert _gaps(paged_attn) < LOGIT_TOL


def test_four_activation_bit_planes_break_the_tolerance():
    assert _gaps("gather", x_bits=4) > LOGIT_TOL

"""The plain reference agrees with the program's own float forward at a
tiny size (CPU, float32 weights): the two are written independently, so
agreement checks both the reference's equations and the weight layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import catalog, sut, weights

TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=101)
#: each configuration at the tiny size; mistral-nemo-12b keeps its q_dim
#: (heads x head_dim) below d_model, as published (4096 < 5120)
TINY_OF = {"qwen3-8b": TINY, "mistral-nemo-12b": {**TINY, "head_dim": 12}}


@pytest.mark.parametrize("config", sorted(TINY_OF))
def test_reference_matches_program_float_forward(config):
    from repro.models.model import forward

    model = {**catalog.config(config), **TINY_OF[config]}
    ref = catalog.reference(model["reference"])
    seed = 2**31 + 17
    params = weights.make_params(seed, model, dtype="float32")
    cfg = dataclasses.replace(sut.model_config(model),
                              param_dtype="float32", compute_dtype="float32")
    toks = np.random.default_rng(0).integers(0, 101, (2, 24)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, jnp.asarray(toks), cfg)
    ends = weights.ends_f32(seed, model, dtype="float32")
    hs = ref.hidden_states(list(toks), model,
                           lambda li: weights.layer_f32(seed, model, li,
                                                        dtype="float32"),
                           ends, pad_to=32, group=2)
    got = np.stack([h @ np.asarray(ends["head"]) for h in hs])
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)


def test_served_gap_is_zero_for_the_references_own_argmax():
    model = {**catalog.config("qwen3-8b"), **TINY}
    ref = catalog.reference(model["reference"])
    rng = np.random.default_rng(1)
    head = jnp.asarray(rng.standard_normal((64, 101)), jnp.float32)
    hidden = rng.standard_normal((10, 64)).astype(np.float32)
    best = np.argmax(hidden @ np.asarray(head), axis=-1)
    gaps = ref.served_gaps(hidden, 4, list(best[3:9]), head, chunk=4)
    assert gaps.shape == (6,) and np.all(gaps == 0)
    wrong = [(int(t) + 1) % 101 for t in best[3:9]]
    assert np.all(ref.served_gaps(hidden, 4, wrong, head) > 0)

"""The paged step samples on the device and returns token ids.

Each call of the scheduler's step ends in the sampler, so what comes back
to the host is one int32 id a row, not the row's logits. The load-bearing
properties, with both paged reads: a greedy token is the first argmax of
the logits ``forward`` computes for the same call, on calls that complete a
prompt and on decode calls; a sampled token is what
``jax.random.categorical`` draws from that row on the host with the key
``jax.random.key((uid << 20) + tokens generated)``; and the copy to the
host, ``sched_d2h_bytes{phase}``, is 4 bytes a row of every call.
"""
import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.models.model import init_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import make_paged_logits, sample_keys

#: uid 3000's seeds pass 31 bits and uid 5000's pass 32
UIDS = (1, 3000, 5000)
PROMPTS = (5, 19, 11)  # one chunk; two chunks across a page; one page+
MAX_NEW = 5
CHUNK = 8


@dataclasses.dataclass
class Call:
    phase: str
    tokens: np.ndarray
    positions: np.ndarray
    last_idx: np.ndarray
    logits: np.ndarray     # forward's logits for the call's rows
    ids: np.ndarray        # what the step returned


def _serve(paged_attn: str, greedy: bool):
    """Serve three requests through two lanes and record every device call
    with ``forward``'s logits for its arguments, read before the step
    consumes its (donated) pool."""
    cfg = reduce_for_smoke(ARCHS["qwen3-8b"])
    params = init_model(jax.random.key(16), cfg)
    rng = np.random.default_rng(16)
    prompts = {u: rng.integers(0, cfg.vocab, n).astype(np.int32)
               for u, n in zip(UIDS, PROMPTS)}
    eng = ServeEngine(cfg, params, batch_size=2, max_len=48, page_size=8,
                      prefill_chunk=CHUNK, paged_attn=paged_attn,
                      greedy=greedy)
    rt = eng._rt
    logits_of = jax.jit(make_paged_logits(rt.cfg))
    real = rt._dispatch
    calls = []

    def dispatch(name, phase, fn, *args):
        logits, _ = logits_of(*args[:6])
        ids, caches = real(name, phase, fn, *args)
        tokens, positions, _, last_idx = args[2:6]
        calls.append(Call(phase, np.asarray(tokens), np.asarray(positions),
                          np.asarray(last_idx), np.asarray(logits),
                          np.asarray(ids)))
        return ids, caches

    rt._dispatch = dispatch
    for u, p in prompts.items():
        eng.submit(Request(uid=u, prompt=p, max_new_tokens=MAX_NEW))
    done = eng.run()
    assert sorted(done) == sorted(UIDS)
    return eng, prompts, done, calls


@pytest.fixture(scope="module")
def served():
    """``_serve``, each case once for this module."""
    return functools.cache(_serve)


def _sampled_rows(prompts, done, calls):
    """(uid, index of the token in ``generated``, call, row) for every
    served token, found by the tokens and positions of each call's rows."""
    seqs = {u: np.concatenate([prompts[u], done[u].generated])
            for u in prompts}
    out = []
    for c in calls:
        for r in range(c.tokens.shape[0]):
            n = int(c.last_idx[r]) + 1
            pos, tok = c.positions[r, :n], c.tokens[r, :n]
            owners = [u for u, s in seqs.items()
                      if pos.max() + 1 < len(s) and np.all(s[pos] == tok)]
            if not owners:
                continue  # a pad row, or the call that fed the last token
            (u,) = owners
            k = int(pos[-1]) + 1 - len(prompts[u])
            if k >= 0:  # the row ends its prompt, or decodes
                out.append((u, k, c, r))
    assert sorted((u, k) for u, k, _, _ in out) == sorted(
        (u, k) for u in prompts for k in range(len(done[u].generated)))
    return out


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_greedy_ids_are_the_argmax_of_forward_logits(served, paged_attn):
    _, prompts, done, calls = served(paged_attn, greedy=True)
    rows = _sampled_rows(prompts, done, calls)
    assert {c.tokens.shape[1] > 1 for _, k, c, _ in rows if k == 0} == {True}
    assert any(c.phase == "decode" for _, _, c, _ in rows)
    for u, k, c, r in rows:
        assert done[u].generated[k] == c.ids[r] == np.argmax(c.logits[r])


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_sampled_ids_follow_the_host_key(served, paged_attn):
    """Each token is the host's draw from its row with the key of a 64-bit
    seed (which ``jax.random.key`` takes whole only with 64-bit types on;
    below 2**32 the two agree)."""
    _, prompts, done, calls = served(paged_attn, greedy=False)
    rows = _sampled_rows(prompts, done, calls)
    for u, k, c, r in rows:
        seed = (u << 20) + k
        with jax.enable_x64(seed >= 2**32):
            key = jax.random.key(seed)
        np.testing.assert_array_equal(jax.random.key_data(key),
                                      sample_keys([seed])[0])
        host = int(jax.random.categorical(key, c.logits[r]))
        assert done[u].generated[k] == c.ids[r] == host
    assert max(u for u, _, _, _ in rows) << 20 >= 2**32


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_d2h_counts_four_bytes_a_row(served, paged_attn):
    eng, _, _, calls = served(paged_attn, greedy=True)
    reg = eng.obs.registry
    for phase in ("prefill", "decode"):
        mine = [c for c in calls if c.phase == phase]
        assert mine and all(c.ids.dtype == np.int32 for c in mine)
        assert reg.get("sched_device_calls").value(phase=phase) == len(mine)
        assert reg.get("sched_d2h_bytes").value(phase=phase) == \
            4 * sum(c.tokens.shape[0] for c in mine)

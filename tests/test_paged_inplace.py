"""The paged KV pool is updated in place by the serving step.

The stacked pool ``[L, n_pages, ps, kv, hd]`` rides the layer scan's carry:
each layer scatters its rows at ``[layer, page, slot]`` and reads its pages
through the layer index, and the jitted step donates the pool. The
load-bearing properties: the compiled step holds no op the size of a whole
layer's pool (no slice of a layer out of the stack, no write-back into it)
and aliases every pool leaf to its output; the pool passed into a call is
deleted by it; both reads give, for a stacked pool and a layer index,
bitwise what they give for that layer's own pool; and the scheduler serves
the offline greedy tokens through COW, prefix hits, defrag and spec rounds
without reading a donated array."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.core.da import DAConfig
from repro.core.engine import get_attn_backend
from repro.core.freeze import freeze_model
from repro.models.model import forward, init_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import pages_for
from repro.serve.scheduler import PagedScheduler, mk_positions
from repro.spec import SpecConfig

KEY = jax.random.key(0)
MAX_NEW = 4
PS = 8

HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
              "int8": "s8"}


def _smoke_cfg(**kw):
    return dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                               moe_dropless=True, **kw)


# ---------------------------------------------------------------------------
# HLO helpers
# ---------------------------------------------------------------------------
def hlo_shape(dtype, dims) -> str:
    return f"{HLO_DTYPES[jnp.dtype(dtype).name]}[{','.join(map(str, dims))}]"


def pool_sized_ops(hlo: str, shapes, kinds=("copy", "dynamic-slice",
                                            "dynamic-update-slice")):
    """Instructions whose result has one of ``shapes`` and whose opcode is
    one of ``kinds``, or a fusion named after one of them."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(",
                     line)
        if not m or m.group(2) not in shapes:
            continue
        name, op = m.group(1), m.group(3)
        if op in kinds or (op == "fusion" and any(k in name for k in kinds)):
            found.append(line.strip()[:160])
    return found


def aliased_pool_params(hlo: str, pool_shapes):
    """(entry parameter numbers holding a pool leaf, parameter numbers the
    module aliases to an output)."""
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\{[\d, ]*\}: \((\d+), \{",
                                          header)}
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[: entry.index("\n}\n")]
    pools = {int(n) for shape, n in re.findall(
        r"= (\w+\[[\d,]*\])\S* parameter\((\d+)\)", entry)
        if shape in pool_shapes}
    return pools, aliased


def _step_args(sched, rows, t):
    return (jnp.zeros((rows, t), jnp.int32),
            mk_positions(sched.cfg,
                         jnp.full((rows, t), sched.pad_pos, jnp.int32)),
            jnp.zeros((rows, sched.W), jnp.int32),
            jnp.zeros((rows,), jnp.int32))


# ---------------------------------------------------------------------------
# the compiled step writes the pool in place and donates it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
@pytest.mark.parametrize("kv_dtype", ["fp16", "int8"])
def test_paged_step_updates_pool_in_place(kv_dtype, paged_attn):
    """The scheduler's compiled step (fused read in interpret mode here):
    no dynamic-slice, dynamic-update-slice or copy the size of one layer's
    pool; every pool leaf aliased input to output; the leaves passed in are
    deleted by the call. The gather read's step also copies no whole stack
    (the Pallas interpreter's own buffers are the fused read's, on the CPU
    only; tests/test_tpu_compile.py checks the compiled kernel's step)."""
    cfg = _smoke_cfg()
    sched = PagedScheduler(cfg, init_model(KEY, cfg), batch_size=2,
                           max_len=32, page_size=PS, paged_attn=paged_attn,
                           kv_dtype=kv_dtype)
    leaves = jax.tree.leaves(sched.caches)
    n_layers = cfg.n_periods
    assert n_layers > 1 and all(a.ndim == 5 and a.shape[0] == n_layers
                                for a in leaves)
    codes = [sched.caches["pos_0"].k, sched.caches["pos_0"].v]
    layer = {hlo_shape(a.dtype, s) for a in codes
             for s in ((1,) + a.shape[1:], a.shape[1:])}
    stack = {hlo_shape(a.dtype, a.shape) for a in leaves}
    for rows, t in ((2, 1), (sched.prefill_lanes, 4)):
        args = _step_args(sched, rows, t)
        hlo = sched._step.lower(sched.params, sched.caches,
                                *args).compile().as_text()
        assert pool_sized_ops(hlo, layer) == []
        if paged_attn == "gather":
            assert pool_sized_ops(hlo, stack) == []
        pools, aliased = aliased_pool_params(hlo, stack)
        assert len(pools) == len(leaves) and pools <= aliased
        before = sched.caches
        _, sched.caches = sched._step(sched.params, before, *args)
        assert all(a.is_deleted() for a in jax.tree.leaves(before))
        assert not any(a.is_deleted() for a in jax.tree.leaves(sched.caches))


# ---------------------------------------------------------------------------
# both reads: a stacked pool at a layer == that layer's own pool, bitwise
# ---------------------------------------------------------------------------
def _stacked_case(rng, fmt, n_layers=3, n_pages=10, ps=PS, t=1):
    b, h, kv, hd = 3, 4, 2, 16
    lens = [ps - 1, 2 * ps + 3, 5]
    w = pages_for(max(lens), ps) + 1
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    if fmt == "fp":
        pool = (n_layers, n_pages, ps, kv, hd)
        k = jnp.asarray(rng.standard_normal(pool), jnp.float32)
        v = jnp.asarray(rng.standard_normal(pool), jnp.float32)
        ks = vs = None
    else:
        hd_p = hd // 2 if fmt == "int4" else hd
        pool = (n_layers, n_pages, ps, kv, hd_p)
        lo, hi = (-128, 128) if fmt == "int4" else (-127, 128)
        k = jnp.asarray(rng.integers(lo, hi, pool), jnp.int8)
        v = jnp.asarray(rng.integers(lo, hi, pool), jnp.int8)
        sshape = pool[:-1] + (1,)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, sshape), jnp.float16)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, sshape), jnp.float16)
    perm = rng.permutation(np.arange(1, n_pages))
    tbl = np.zeros((b, w), np.int32)
    tpos = np.zeros((b, t), np.int32)
    pi = 0
    for i, ln in enumerate(lens):
        npg = pages_for(ln, ps)
        tbl[i, :npg] = perm[pi:pi + npg]
        pi += npg
        tpos[i] = np.maximum(np.arange(ln - t, ln), 0)
    return q, k, v, ks, vs, jnp.asarray(tbl), jnp.asarray(tpos)


@pytest.mark.parametrize("fmt", ["fp", "int8", "int4"])
@pytest.mark.parametrize("backend", ["fused", "gather"])
def test_stacked_pool_read_equals_layer_pool(backend, fmt):
    """Given the period stack and a layer index (a Python int, and a traced
    one under jit), each read returns bitwise what it returns for that
    layer's own 4-D pool (the fused kernel in interpret mode)."""
    rng = np.random.default_rng(3)
    q, k, v, ks, vs, tbl, tpos = _stacked_case(rng, fmt, t=2)
    fn = get_attn_backend(backend).fn

    def read(layer, k, v, ks, vs):
        return fn(q, k, v, tbl, tpos, k_scale=ks, v_scale=vs, layer=layer)

    traced = jax.jit(read)
    for layer in range(k.shape[0]):
        one = [None if a is None else a[layer] for a in (k, v, ks, vs)]
        want = np.asarray(read(None, *one))
        np.testing.assert_array_equal(np.asarray(read(layer, k, v, ks, vs)),
                                      want)
        np.testing.assert_array_equal(
            np.asarray(traced(jnp.int32(layer), k, v, ks, vs)), want)


def test_stacked_pool_needs_layer_and_layer_needs_stack():
    rng = np.random.default_rng(4)
    q, k, v, _, _, tbl, tpos = _stacked_case(rng, "fp")
    fused = get_attn_backend("fused").fn
    with pytest.raises(ValueError, match="layer index"):
        fused(q, k, v, tbl, tpos)
    with pytest.raises(ValueError, match="single-layer"):
        fused(q, k[0], v[0], tbl, tpos, layer=0)


# ---------------------------------------------------------------------------
# serving: offline greedy tokens through COW, prefix hits, defrag and spec
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    cfg = _smoke_cfg()
    params = init_model(KEY, cfg)
    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, 2 * PS + 2)
    prompts = {u: np.concatenate([shared, rng.integers(0, cfg.vocab, 3 + u)])
               for u in range(4)}
    prompts[4] = prompts[3].copy()  # a twin: its prefix hit ends mid-page

    def offline(p):
        refs = {}
        for uid, pr in prompts.items():
            toks = list(pr)
            for _ in range(MAX_NEW):
                lg, _ = forward(p, jnp.asarray(toks, jnp.int32)[None], cfg)
                toks.append(int(jnp.argmax(lg[0, -1])))
            refs[uid] = toks[len(pr):]
        return refs

    return cfg, {"float": params, "frozen": art.params}, prompts, offline


@pytest.mark.parametrize("provider", ["bitplane", "layerskip", "artifact"])
def test_serve_offline_tokens_through_cow_prefix_defrag_spec(served,
                                                             provider):
    """Prefix cache on, a twin prompt (a prefix hit, then a COW copy of the
    last shared page), a defrag mid-run and spec rounds — the draft sharing
    the target's pool (bitplane, layerskip) or carrying its own (artifact):
    every request gets its offline greedy tokens, no call reads a donated
    pool (a deleted array raises), and the pools the engine started with
    were donated."""
    cfg, trees, prompts, offline = served
    if provider == "bitplane":
        tree = "frozen"
        spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=6,
                          disable_below=0.0)
    elif provider == "layerskip":
        tree = "float"
        spec = SpecConfig(provider="layerskip", gamma=2, draft_periods=1,
                          disable_below=0.0)
    else:
        tree = "float"
        dcfg = dataclasses.replace(cfg, n_layers=1, name="draft")
        spec = SpecConfig(provider="artifact", gamma=2,
                          draft_params=init_model(jax.random.key(1), dcfg),
                          draft_model_cfg=dcfg, disable_below=0.0)
    eng = ServeEngine(cfg, trees[tree], batch_size=2, max_len=48,
                      page_size=PS, prefix_cache=True, spec=spec)
    rt = eng._rt
    first = jax.tree.leaves((rt.caches, rt.draft_caches))
    for uid, pr in prompts.items():
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
    for _ in range(6):
        eng.step()
    rt.defrag()
    done = eng.run()
    assert {u: list(r.generated) for u, r in done.items()} == offline(
        trees[tree])
    m = eng.metrics()
    assert m["spec"]["rounds"] > 0
    assert m["prefix_cache"]["hits"] >= 2
    assert m["prefix_cache"]["cow_copies"] >= 1
    assert all(a.is_deleted() for a in first)
    live = jax.tree.leaves((rt.caches, rt.draft_caches))
    assert not any(a.is_deleted() for a in live)

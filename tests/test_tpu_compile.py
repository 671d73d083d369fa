"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) v5e chip by the TPU compiler, which refuses what interpret mode
accepts — blocks off the (8, 128) tiling, accumulators narrower than 32
bits, loads of types the chip lacks. Shapes are those the served qwen3-8b
path hits: batch 8, 32 query / 8 KV heads of width 128, 16-token pages over
a 1024-token context (and the fused read at 8192), and the DA VMMs of one
full-width layer.

The topology is described inside a module fixture (never at import): only
the test worker that runs this file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.da import DAConfig
from repro.kernels.bitplane_vmm import bitplane_vmm_pallas
from repro.kernels.da_vmm import da_vmm_pallas
from repro.kernels.paged_attention import paged_attention
from repro.serve.kvcache import pages_for, table_width

B, H, KV, HD, PS, MAX_LEN = 8, 32, 8, 128, 16, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("t", [1, 16])
@pytest.mark.parametrize("kv_dtype,max_len", [
    pytest.param("fp16", MAX_LEN, id="fp16"),
    pytest.param("int8", MAX_LEN, id="int8"),
    pytest.param("int4", MAX_LEN, id="int4"),
    pytest.param("fp16", 8192, id="fp16-8192"),
])
def test_fused_paged_attention_compiles(one_chip, kv_dtype, max_len, t):
    """At ``max_len`` 8192 too: the kernel's VMEM holds a block of keys, not
    the row's whole table, so it fits whatever the context."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_pages = 1 + B * pages_for(max_len, PS)
    w = table_width(max_len, PS)
    pool = (n_pages, PS, KV, HD)
    q, table, tpos = (s((B, t, H, HD), jnp.bfloat16), s((B, w), jnp.int32),
                      s((B, t), jnp.int32))
    if kv_dtype == "fp16":
        compiled = _compile(
            lambda *a: paged_attention(*a, interpret=False),
            q, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16), table, tpos)
    else:  # int8 codes, or int4 nibble pairs packed along hd
        codes = pool[:3] + (HD // 2 if kv_dtype == "int4" else HD,)
        scale = (n_pages, PS, KV, 1)
        compiled = _compile(
            lambda q, k, v, tb, tp, ks, vs: paged_attention(
                q, k, v, tb, tp, k_scale=ks, v_scale=vs, interpret=False),
            q, s(codes, jnp.int8), s(codes, jnp.int8), table, tpos,
            s(scale, jnp.float16), s(scale, jnp.float16))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_bitplane_compiles(one_chip):
    cfg = DAConfig(x_signed=True)
    compiled = _compile(
        lambda x, w: bitplane_vmm_pallas(x, w, cfg, interpret=False),
        jax.ShapeDtypeStruct((8, 4096), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096, 4096), jnp.int8, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_lut_compiles(one_chip):
    cfg = DAConfig(x_signed=True)
    m, k, n = 8, 512, 1024
    groups = k // cfg.group_size
    compiled = _compile(
        lambda x, luts: da_vmm_pallas(x, luts, cfg, interpret=False),
        jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((groups, 1 << cfg.group_size, n), jnp.int32,
                             sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype,d_model", [
    pytest.param("fp16", 256, id="fp16"),
    pytest.param("int8", 256, id="int8"),
    pytest.param("fp16", H * HD + 64, id="fp16-model-wider-than-heads"),
])
def test_paged_step_updates_pool_in_place_on_v5e(one_chip, monkeypatch,
                                                  kv_dtype, d_model):
    """The serving step with the fused read compiled (not interpreted), its
    pool donated: no copy, dynamic-slice or dynamic-update-slice the size of
    one layer's K/V pool or of their whole stack, and every pool leaf
    aliased to its output. A small model at the kernel's real head widths,
    narrower than its heads (d_model 256) or, as mistral-nemo-12b (5120
    against 32 x 128), wider: then ``wq`` narrows, ``wo`` widens and the
    fused QKV product has K != N. (The int8 scale stacks, 1/hd of the
    codes, still change layout on the way into and out of the layer loop;
    not asserted here.)"""
    import dataclasses

    import repro.kernels.paged_attention as pa
    from repro.configs.registry import ARCHS, reduce_for_smoke
    from repro.models.model import init_model
    from repro.serve.kvcache import init_paged_caches
    from repro.serve.scheduler import make_paged_step
    from test_paged_inplace import (aliased_pool_params, hlo_shape,
                                    pool_sized_ops)

    monkeypatch.setattr(pa, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(
        reduce_for_smoke(ARCHS["qwen3-8b"]), d_model=d_model, n_heads=H,
        n_kv_heads=KV, head_dim=HD, d_ff=512, vocab=512,
        param_dtype="bfloat16", compute_dtype="bfloat16",
        paged_attn="fused", kv_dtype=kv_dtype)
    max_len, b = 128, 8
    n_pages = 1 + b * pages_for(max_len, PS)
    kvds = None if kv_dtype == "fp16" else {"pos_0": kv_dtype}

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_model(jax.random.key(0),
                                                       cfg)))
    caches = on_chip(jax.eval_shape(lambda: init_paged_caches(
        cfg, n_pages, PS, cfg.dtype(), kv_dtypes=kvds)))
    leaves = jax.tree.leaves(caches)
    codes = [caches["pos_0"].k, caches["pos_0"].v]
    layer = {hlo_shape(a.dtype, s) for a in codes
             for s in ((1,) + a.shape[1:], a.shape[1:])}
    stack = {hlo_shape(a.dtype, a.shape) for a in codes}
    w = table_width(max_len, PS)
    step = jax.jit(make_paged_step(cfg), donate_argnums=(1,))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    for rows, t in ((b, 1), (4, 16)):
        hlo = step.lower(params, caches, i32(rows, t), i32(rows, t),
                         i32(rows, w), i32(rows)).compile().as_text()
        assert "tpu_custom_call" in hlo
        # the step samples on the chip: one int32 token id a row comes back
        out = hlo.splitlines()[0].split(")->", 1)[1]
        assert re.match(r"\((\w+\[[\d,]*\])", out).group(1) == \
            f"s32[{rows}]"
        assert pool_sized_ops(hlo, layer | stack) == []
        pools, aliased = aliased_pool_params(
            hlo, {hlo_shape(a.dtype, a.shape) for a in leaves})
        assert len(pools) == len(leaves) and pools <= aliased

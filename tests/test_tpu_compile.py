"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) v5e chip by the TPU compiler, which refuses what interpret mode
accepts — blocks off the (8, 128) tiling, accumulators narrower than 32
bits, loads of types the chip lacks. Shapes are those the served qwen3-8b
path hits: batch 8, 32 query / 8 KV heads of width 128, 16-token pages over
a 1024-token context, and the DA VMMs of one full-width layer.

The topology is described inside a module fixture (never at import): only
the test worker that runs this file loads the TPU compiler.
"""
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
@pytest.mark.parametrize("kv_dtype", ["fp16", "int8", "int4"])
def test_fused_paged_attention_compiles(one_chip, kv_dtype, t):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_pages = 1 + B * pages_for(MAX_LEN, PS)
    w = table_width(MAX_LEN, PS)
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

"""Compile a cell's step shapes for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/compile_rehearsal.py \
        qwen3-8b

For each configuration: the frozen parameter tree, the paged KV pool and
the engine's step are built from shapes alone (``jax.eval_shape``), then
the widest decode step [batch, 1] and the prefill step [lanes, chunk] are
lowered and compiled by the TPU compiler for one chip of a described
``v5e:2x2``. Nothing runs and nothing is allocated: this catches what the
chip's compiler refuses, and reports the compiled program's memory, before
any chip time is spent. The layers are planned as the chip plans them
(``bitplane_stacked``, with the fused paged read compiled, not
interpreted).
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def rehearse(name: str, one_chip) -> None:
    import jax
    import jax.numpy as jnp

    import repro.kernels.paged_attention as pa
    from bench import catalog, sut, weights
    from repro.core.da import DAConfig
    from repro.core.freeze import freeze_model
    from repro.serve.kvcache import init_paged_caches, pages_for, table_width
    from repro.serve.scheduler import make_paged_step

    pa._default_interpret = lambda: False  # compile the kernel, as on a TPU
    model = catalog.config(name)
    serving = model["serving"]
    cfg = dataclasses.replace(sut.model_config(model), paged_attn="fused",
                              prefill_last_only=True)
    b, max_len, ps = serving["batch"], serving["max_len"], \
        serving["page_size"]
    floats = jax.eval_shape(lambda: weights.make_params(0, model))
    frozen = jax.eval_shape(
        lambda p: freeze_model(p, DAConfig(x_signed=True),
                               mode="bitplane_stacked", m_hint=b,
                               model_cfg=cfg).params, floats)
    n_pages = b * pages_for(max_len, ps) + 1
    caches = jax.eval_shape(lambda: init_paged_caches(
        cfg, n_pages, ps, cfg.dtype()))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    step = jax.jit(make_paged_step(cfg))
    w = table_width(max_len, ps)
    lanes = min(4, b)
    for rows, t in ((b, 1), (lanes, serving["prefill_chunk"])):
        args = on_chip((frozen, caches,
                        jax.ShapeDtypeStruct((rows, t), jnp.int32),
                        jax.ShapeDtypeStruct((rows, t), jnp.int32),
                        jax.ShapeDtypeStruct((rows, w), jnp.int32),
                        jax.ShapeDtypeStruct((rows,), jnp.int32)))
        t0 = time.perf_counter()
        compiled = step.lower(*args).compile()
        mem = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"{name} step [{rows}x{t}]: compiled in "
              f"{time.perf_counter() - t0:.1f}s; arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, outputs "
              f"{mem.output_size_in_bytes / 2**30:.2f} GiB; "
              f"{kernels} tpu_custom_call", flush=True)


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        rehearse(name, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["qwen3-8b"]))

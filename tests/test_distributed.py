"""Distribution tests on an 8-device CPU mesh (subprocess: jax locks the
device count at first init, so these run with their own XLA_FLAGS)."""
import os
import subprocess
import sys
import textwrap

import pytest

# Each test compiles a sharded model in a fresh subprocess — multi-second by
# construction. Run with `pytest -m slow`.
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """A tiny model train step on a (2,4) mesh == the unsharded step."""
    out = run_with_devices("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import ARCHS, reduce_for_smoke
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharding import use_mesh_rules
        from repro.launch import specs as SP
        from repro.train.trainer import TrainConfig, init_state, make_train_step
        from repro.data.pipeline import DataConfig, batch_at

        cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                                  d_model=64, d_ff=128, n_layers=2)
        dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=0)
        batch = jax.tree.map(jnp.asarray, batch_at(dc, 0))
        state = init_state(jax.random.key(0), cfg)
        step = make_train_step(cfg, TrainConfig())
        # single-device reference
        s_ref, m_ref = jax.jit(step)(state, batch)

        mesh = make_test_mesh((2, 4), ("data", "model"))
        with use_mesh_rules(mesh):
            sspec = SP.tree_pspecs(state)
            bspec = SP.batch_pspecs(batch)
            to_ns = lambda t: jax.tree.map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda x: isinstance(x, P))
            st = jax.device_put(state, to_ns(sspec))
            bt = jax.device_put(batch, to_ns(bspec))
            s_sh, m_sh = jax.jit(
                step, in_shardings=(to_ns(sspec), to_ns(bspec)),
                out_shardings=(to_ns(sspec), None))(st, bt)
        d = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
        assert d < 1e-3, d
        w_ref = jax.tree.leaves(s_ref.params)[0]
        w_sh = jax.tree.leaves(s_sh.params)[0]
        np.testing.assert_allclose(np.asarray(w_ref), np.asarray(w_sh),
                                   atol=5e-3)
        print("OK", float(m_sh["loss"]))
    """)
    assert "OK" in out


def test_dryrun_cells_on_small_mesh():
    """Miniature of the production dry-run: lower+compile train/prefill/
    decode for a tiny arch on 2-D and 3-D meshes; roofline terms > 0."""
    out = run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import ARCHS, reduce_for_smoke, ShapeSpec
        from repro.launch.mesh import make_test_mesh
        from repro.launch.dryrun import lower_cell, _cost_of
        from repro.launch import roofline as rl

        cfg0 = dataclasses.replace(reduce_for_smoke(ARCHS["jamba-1.5-large-398b"]),
                                   param_dtype="bfloat16", compute_dtype="bfloat16")
        import repro.configs.registry as REG
        REG.ARCHS["tiny-jamba"] = cfg0

        for axes, shape in [(("data","model"),(2,4)), (("pod","data","model"),(2,2,2))]:
            mesh = make_test_mesh(shape, axes)
            for sname, kind, sl, gb in [("train_4k","train",32,8),
                                         ("prefill_32k","prefill",32,8),
                                         ("decode_32k","decode",32,8)]:
                spec = ShapeSpec(sname, sl, gb, kind)
                lowered, aux = lower_cell(cfg0, spec, mesh)
                compiled = lowered.compile()
                cost = _cost_of(compiled)
                assert cost["flops"] > 0
                mem = compiled.memory_analysis()
                print("OK", axes, sname, int(cost["flops"]), cost["coll"] >= 0)
    """)
    assert out.count("OK") == 6


def test_pipeline_parallel_correctness():
    """GPipe schedule over a 4-stage axis == sequential stage application."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.train.pipeline import make_pipelined_apply

        mesh = make_test_mesh((4,), ("stage",))
        S, M, mb, D = 4, 8, 4, 16
        key = jax.random.key(0)
        ws = jax.random.normal(key, (S, D, D)) * 0.3

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        apply = make_pipelined_apply(mesh, "stage", stage_fn, n_microbatches=M)
        x = jax.random.normal(jax.random.key(1), (M * mb, D))
        sw = jax.device_put(ws, NamedSharding(mesh, P("stage")))
        y = apply(sw, x)
        ref = x
        for s in range(S):
            ref = stage_fn(ws[s], ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_allreduce_dp():
    """int8+EF all-reduce inside shard_map: mean grad ≈ true mean; EF keeps
    the accumulated error bounded."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.optim.compress import allreduce_compressed, init_error

        mesh = make_test_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.key(0), (8, 64))

        def f(g_local, err):
            mean, new_err = allreduce_compressed({"w": g_local}, err, "data")
            return mean["w"], new_err

        f_sh = jax.shard_map(f, mesh=mesh,
                             in_specs=(P("data"), {"w": P()}),
                             out_specs=(P(), {"w": P()}),
                             check_vma=False)
        err0 = init_error({"w": jnp.zeros((64,))})
        mean, err = f_sh(g, err0)
        true_mean = jnp.mean(g, axis=0)
        rel = float(jnp.abs(mean[0] - true_mean).max() / jnp.abs(true_mean).max())
        assert rel < 0.05, rel
        print("OK", rel)
    """)
    assert "OK" in out


def test_da_serving_under_sharding():
    """DA bitplane serving path lowers and runs under a model-parallel mesh
    (the paper's technique inside the distributed serving graph)."""
    out = run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import ARCHS, reduce_for_smoke
        from repro.core.da import DAConfig
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharding import use_mesh_rules
        from repro.models.model import forward, init_model
        from repro.core.freeze import freeze_model_da

        cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                                  moe_dropless=True)
        params = init_model(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab)
        ref, _ = forward(params, toks, cfg)
        frozen = freeze_model_da(params, DAConfig(x_signed=True),
                                 mode="da_bitplane")
        mesh = make_test_mesh((2, 4), ("data", "model"))
        with use_mesh_rules(mesh):
            got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(frozen, toks)
        agree = float(np.mean(np.asarray(
            jnp.argmax(ref, -1) == jnp.argmax(got, -1))))
        assert agree > 0.8, agree
        print("OK", agree)
    """)
    assert "OK" in out


def test_frozen_artifact_shards_pmas_over_model_axis():
    """The artifact pipeline's shard stage: a DA-frozen model's packed
    leaves (wq / w_scale / luts) tensor-parallel over the mesh's model axis
    — codes, scales and LUT slabs of one column slice co-located — and the
    sharded serving forward matches the unsharded one (integer DA path is
    exact; float epilogues differ only by reduction-order noise)."""
    out = run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import ARCHS, reduce_for_smoke
        from repro.core.da import DAConfig
        from repro.core.freeze import freeze_model
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharding import shard_frozen_params, use_mesh_rules
        from repro.models.model import forward, init_model

        cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                                  moe_dropless=True)
        params = init_model(jax.random.key(0), cfg)
        art = freeze_model(params, DAConfig(x_signed=True), mode="lut")
        toks = jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab)
        ref, _ = forward(art.params, toks, cfg)

        mesh = make_test_mesh((2, 4), ("data", "model"))
        with use_mesh_rules(mesh):
            sharded = shard_frozen_params(art.params)
            # attention out-projection: [P, K, N] codes split N 4-ways,
            # with the scale and the LUT slab split the same way
            pw = sharded["periods"]["pos_0"]["mixer"]["wq"]
            for leaf, want_axis in ((pw.wq, -1), (pw.w_scale, -1),
                                    (pw.luts, -1)):
                spec = leaf.sharding.spec
                assert spec and spec[-1] == "model", (leaf.shape, spec)
                assert leaf.addressable_shards[0].data.shape[want_axis] \\
                    == leaf.shape[want_axis] // 4
            got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(sharded, toks)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   atol=1e-4, rtol=1e-4)
        assert bool(jnp.all(jnp.argmax(ref, -1) == jnp.argmax(got, -1)))
        print("OK", pw.wq.sharding.spec)
    """)
    assert "OK" in out


def test_fsdp_rules_shard_params_2d():
    """FSDP/ZeRO-style 2-D sharding: weights shard over data AND model axes;
    per-device parameter bytes shrink by the full mesh size."""
    out = run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, math
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import ARCHS, reduce_for_smoke
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharding import FSDP_RULES, use_mesh_rules
        from repro.launch import specs as SP
        from repro.train.trainer import init_state, make_train_step, TrainConfig
        from repro.data.pipeline import DataConfig, batch_at

        cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                                  d_model=64, d_ff=128, n_layers=2)
        mesh = make_test_mesh((2, 4), ("data", "model"))
        state = init_state(jax.random.key(0), cfg)
        with use_mesh_rules(mesh, FSDP_RULES):
            sspec = SP.tree_pspecs(state)
        # the MLP weight must now carry BOTH axes
        spec = sspec.params["periods"]["pos_0"]["ffn"]["w_up"]
        assert "data" in str(spec) and "model" in str(spec), spec
        # and the train step still runs + matches the unsharded loss
        to_ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=0)
        batch = jax.tree.map(jnp.asarray, batch_at(dc, 0))
        step = make_train_step(cfg, TrainConfig())
        _, m_ref = jax.jit(step)(state, batch)
        with use_mesh_rules(mesh, FSDP_RULES):
            sspec = SP.tree_pspecs(state)
            bspec = SP.batch_pspecs(batch)
            st = jax.device_put(state, to_ns(sspec))
            bt = jax.device_put(batch, to_ns(bspec))
            _, m_sh = jax.jit(step, in_shardings=(to_ns(sspec), to_ns(bspec)),
                              out_shardings=(to_ns(sspec), None))(st, bt)
        assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3
        print("OK", spec)
    """)
    assert "OK" in out

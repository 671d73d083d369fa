"""Record the small TPU capture that test_tracing.py reads (run on a TPU).

    python bench/tests/record_probe.py   # writes bench/tests/data/

A jitted step — a lax.scan of four DA matmuls (bitplane_stacked) and one
fused paged-attention read — runs three times under the profiler. The
capture (``probe.xplane.pb``) and the step's optimized HLO
(``probe_hlo.txt``) are what the trace reduction is checked against.
"""
import glob
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.core.da import DAConfig
    from repro.core.engine import da_matmul, get_attn_backend, pack_weights

    if jax.devices()[0].platform != "tpu":
        print("record_probe: needs a TPU", file=sys.stderr)
        return 3
    w = jax.random.normal(jax.random.key(0), (4, 1024, 2048), jnp.bfloat16)
    packs = jax.vmap(lambda wi: pack_weights(
        wi, DAConfig(x_signed=True), mode="bitplane_stacked",
        with_luts=False))(w)
    fused = get_attn_backend("fused").fn
    ps, kv, hd, heads = 16, 8, 128, 32
    kp = jax.random.normal(jax.random.key(1), (1 + 8 * 64, ps, kv, hd),
                           jnp.bfloat16)
    vp = kp * 0.5
    table = jnp.arange(1, 1 + 8 * 64, dtype=jnp.int32).reshape(8, 64)
    tpos = jnp.full((8, 1), 700, jnp.int32)

    def body(h, p):
        y = da_matmul(h, p)
        return h + y[:, :1024].astype(h.dtype), None

    @jax.jit
    def step(x, packs, q):
        h, _ = jax.lax.scan(body, x, packs)
        with jax.named_scope("outer_attn"):
            o = fused(q, kp, vp, table, tpos, softmax_dtype="float32",
                      mask_mode="where")
        return h, o

    x = jax.random.normal(jax.random.key(2), (8, 1024), jnp.bfloat16)
    q = jax.random.normal(jax.random.key(3), (8, 1, heads, hd), jnp.bfloat16)
    jax.block_until_ready(step(x, packs, q))
    data = HERE / "data"
    data.mkdir(exist_ok=True)
    (data / "probe_hlo.txt").write_text(
        step.lower(x, packs, q).compile().as_text())
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"hostspan{i}"):
            jax.block_until_ready(step(x, packs, q))
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                data / "probe.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serve qwen3-8b at its published widths on one TPU through ServeEngine.

The quickest proof that the system's main path runs on the chip. One
process, in order:

1. every registered DA backend, once, bit-for-bit against the integer
   oracle ``xq @ wq`` (no-LUT backends at a full-width 8x4096x4096 layer,
   LUT backends at 8x512x1024, inside the planner's LUT budget);
2. the fused Pallas paged-attention read against the XLA gather read at the
   served shapes, for decode (T=1) and prefill chunks (T=16), on
   compute-dtype, int8 and packed-int4 KV pages;
3. qwen3-8b at its published widths, depth cut to 4 of 36 layers and random
   weights from ``--seed``, frozen by ``ServeEngine(da_mode="auto")`` and
   served by the paged runtime: 8 requests of 128-512 prompt tokens, 32 new
   tokens each, after ``warmup()``.

Any failed check raises, and the process exits nonzero. The last line of
standard output is one JSON object naming the device. Without a TPU the
script exits nonzero and prints no result, unless ``--rehearse`` asks for
the same phases on whatever device JAX has, at a tiny size.

    python chip_smoke.py              # on a TPU host
    python chip_smoke.py --rehearse   # anywhere: tiny shapes, same phases

Device time is not measured here; the seconds printed are host wall time,
compilation included.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: The served configuration's layers. qwen3-8b has 36; ServeEngine freezes
#: from a float tree already on the device, and all 36 layers in bfloat16
#: (16.4 GB with embeddings and head) exceed the chip's 16 GB. Four layers
#: hold about 4.0 GB of float weights beside 1.4 GB of packed codes.
SERVED_LAYERS = 4


@dataclasses.dataclass(frozen=True)
class Size:
    """Shapes of one smoke run."""

    n_layers: int
    batch: int
    requests: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int
    max_len: int
    page_size: int
    vmm_nolut: tuple   # (M, K, N) for backends without LUTs
    vmm_lut: tuple     # (M, K, N) for LUT backends


CHIP = Size(n_layers=SERVED_LAYERS, batch=8, requests=8, prompt_lo=128,
            prompt_hi=512, new_tokens=32, max_len=1024, page_size=16,
            vmm_nolut=(8, 4096, 4096), vmm_lut=(8, 512, 1024))
REHEARSAL = Size(n_layers=2, batch=2, requests=4, prompt_lo=8, prompt_hi=24,
                 new_tokens=4, max_len=64, page_size=8,
                 vmm_nolut=(8, 256, 256), vmm_lut=(8, 64, 128))


def model_config(rehearse: bool):
    from repro.configs.registry import ARCHS, reduce_for_smoke

    cfg = ARCHS["qwen3-8b"]
    if rehearse:
        return dataclasses.replace(reduce_for_smoke(cfg),
                                   n_layers=REHEARSAL.n_layers)
    return dataclasses.replace(cfg, n_layers=SERVED_LAYERS)


def check_da_backends(size: Size, seed: int) -> None:
    """Every registered DA backend, bit-exact against int32 ``xq @ wq``."""
    import jax
    import numpy as np

    from repro.core.da import DAConfig
    from repro.core.engine import jit_backend, pack_quantized, \
        registered_backends

    cfg = DAConfig(x_signed=True)
    rng = np.random.default_rng(seed)
    for name, spec in sorted(registered_backends().items()):
        m, k, n = size.vmm_lut if spec.needs_luts else size.vmm_nolut
        xq = rng.integers(-128, 128, (m, k)).astype(np.int32)
        wq = rng.integers(-127, 128, (k, n)).astype(np.int8)
        packed = pack_quantized(wq, cfg=cfg, with_luts=spec.needs_luts)
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(
            jit_backend(spec, cfg)(jax.numpy.asarray(xq), packed)))
        secs = time.perf_counter() - t0
        want = xq.astype(np.int64) @ wq.astype(np.int64)
        if got.dtype != np.int32 or not np.array_equal(got, want):
            bad = int(np.count_nonzero(got != want))
            raise AssertionError(
                f"DA backend {name!r} at {m}x{k}x{n}: {bad} of {got.size} "
                f"outputs differ from xq @ wq (dtype {got.dtype})")
        print(f"da backend {name:17s} {m}x{k}x{n:<5d} bit-exact vs xq@wq "
              f"(host wall incl. compile {secs:.1f}s)")


def _paged_case(rng, b, t, heads, kv, hd, ps, max_len, dtype):
    """Random pool + permuted page tables + ragged tpos, as served."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.kvcache import pages_for, table_width

    w = table_width(max_len, ps)
    n_pages = 1 + b * pages_for(max_len, ps)
    q = jnp.asarray(rng.standard_normal((b, t, heads, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)), dtype)
    perm = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((b, w), np.int32)
    tpos = np.zeros((b, t), np.int32)
    used = 0
    for i, length in enumerate(rng.integers(t + 1, max_len + 1, b)):
        npg = pages_for(int(length), ps)
        table[i, :npg] = perm[used:used + npg]
        used += npg
        tpos[i] = np.arange(length - t, length)
    return q, k, v, jnp.asarray(table), jnp.asarray(tpos)


def check_paged_attention(cfg, size: Size, seed: int) -> None:
    """Fused page walk vs gather read at the served shapes, for every KV page
    format the paged runtime serves (compute-dtype, int8, packed int4).

    Tolerance: both reads dequantize pages with the same elementwise formula
    and round the softmax probabilities and the output to the compute dtype
    once, from float32 values that differ only in the order of their sums
    and in how each compiler lowers exp and divide. So an element may land a
    few ulps of the compute dtype apart, relative to the largest output; a
    wrong page, scale or mask moves outputs by a share of the attention
    mass, orders of magnitude more. The bound is 4 ulps at the largest
    |gather| output.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import get_attn_backend
    from repro.models import kv_quant

    rng = np.random.default_rng(seed)
    dtype = cfg.dtype()
    kw = dict(softmax_dtype=cfg.softmax_dtype, mask_mode=cfg.attn_mask_mode)
    reads = {name: jax.jit(
        lambda q, k, v, table, tpos, ks, vs, _f=get_attn_backend(name).fn:
        _f(q, k, v, table, tpos, k_scale=ks, v_scale=vs, **kw))
        for name in ("gather", "fused")}
    for t in (1, 16):
        q, k, v, table, tpos = _paged_case(
            rng, size.batch, t, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_,
            size.page_size, size.max_len, dtype)
        for fmt in kv_quant.KV_DTYPES:
            if fmt == "fp16":  # pages at the compute dtype, no scales
                case = (q, k, v, table, tpos, None, None)
            else:
                (kc, ks), (vc, vs) = (kv_quant.quantize_kv(k, fmt),
                                      kv_quant.quantize_kv(v, fmt))
                case = (q, kc, vc, table, tpos, ks, vs)
            if jax.devices()[0].platform == "tpu" and "tpu_custom_call" not \
                    in reads["fused"].lower(*case).as_text():
                raise AssertionError(
                    f"the fused read ({fmt} pages) lowered without its kernel")
            ref = np.asarray(reads["gather"](*case).astype(jnp.float32))
            out = np.asarray(reads["fused"](*case).astype(jnp.float32))
            if not (np.isfinite(ref).all() and np.isfinite(out).all()):
                raise AssertionError(
                    f"paged attention T={t} {fmt} pages: non-finite output")
            diff = float(np.max(np.abs(out - ref)))
            top = float(np.max(np.abs(ref)))
            tol = 4 * float(jnp.finfo(dtype).eps) * 2.0 ** np.floor(
                np.log2(top))
            print(f"paged attention T={t:<2d} {fmt:4s} pages fused vs gather:"
                  f" max|diff| {diff:.3e} (tolerance {tol:.3e}, max|out| "
                  f"{top:.3e})")
            if diff > tol:
                raise AssertionError(
                    f"paged attention T={t} {fmt} pages: fused differs from "
                    f"gather by {diff} > {tol}")


def serve(cfg, size: Size, seed: int, rehearse: bool) -> None:
    """Freeze and serve through ServeEngine, checking what comes out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import ARCHS
    from repro.core.engine import get_backend, select_attn_backend
    from repro.models.model import count_params, init_model
    from repro.serve.engine import Request, ServeEngine

    print(f"model {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} head_dim={cfg.head_dim_} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} qk_norm={cfg.qk_norm} dtype={cfg.param_dtype} "
          f"layers={cfg.n_layers} ({count_params(cfg) / 1e9:.3f}B params)")
    if not rehearse:
        print(f"depth cut: {ARCHS['qwen3-8b'].n_layers} -> {cfg.n_layers} "
              "layers, widths as published (the float tree frozen on the "
              "device must fit 16 GB beside the packed codes)")

    t0 = time.perf_counter()
    params = init_model(jax.random.key(seed), cfg)
    eng = ServeEngine(cfg, params, batch_size=size.batch,
                      max_len=size.max_len, da_mode="auto", runtime="paged",
                      page_size=size.page_size, paged_attn="auto")
    del params  # the float tree: only the frozen artifact is served
    print(f"init + freeze: {time.perf_counter() - t0:.1f}s host wall")

    plans = eng.artifact.plan
    for key in sorted(plans):
        p = plans[key]
        print(f"  da layer {key:28s} {p.k}x{p.n:<7d} mode={p.mode:17s} "
              f"luts={p.with_luts} ({p.source})")
        if not get_backend(p.mode).is_da:
            raise AssertionError(f"{key}: planned a non-DA backend {p.mode}")
        if not rehearse and p.with_luts:
            raise AssertionError(f"{key}: LUTs at published widths")

    want = "fused" if jax.devices()[0].platform == "tpu" else "gather"
    picked = select_attn_backend(eng.cfg.paged_attn)
    print(f"attention backend: {picked}")
    if picked != want:
        raise AssertionError(
            f"paged_attn='auto' picked {picked!r}; expected {want!r} on "
            f"{jax.devices()[0].platform}")

    rng = np.random.default_rng(seed)
    for uid in range(size.requests):
        n = int(rng.integers(size.prompt_lo, size.prompt_hi + 1))
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, n),
                           max_new_tokens=size.new_tokens))
    t0 = time.perf_counter()
    shapes = eng.warmup()
    compiles = eng.metrics()["step_compiles"]
    print(f"warmup: {shapes} step shapes compiled in "
          f"{time.perf_counter() - t0:.1f}s host wall")
    done = eng.run()
    m = eng.metrics()
    in_run = m["step_compiles"] - compiles
    print(f"run: {m['requests_done']}/{size.requests} requests, "
          f"{m['out_tokens']} tokens, {m['steps']} scheduler ticks, "
          f"{in_run} step compiles inside run()")
    if in_run:
        raise AssertionError(f"{in_run} step shapes compiled inside run()")
    if sorted(done) != list(range(size.requests)):
        raise AssertionError(f"finished {sorted(done)} of {size.requests}")
    for uid, req in sorted(done.items()):
        toks = np.asarray(req.generated)
        if len(toks) != size.new_tokens or toks.min() < 0 \
                or toks.max() >= cfg.vocab:
            raise AssertionError(f"request {uid}: tokens {req.generated}")
    finite = all(bool(jnp.isfinite(leaf).all())
                 for leaf in jax.tree.leaves(eng.caches)
                 if jnp.issubdtype(leaf.dtype, jnp.floating))
    print(f"tokens per request: {[len(r.generated) for r in done.values()]}"
          f"; KV pool finite: {finite}")
    if not finite:
        raise AssertionError("NaN or inf in the KV pool after serving")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases at a tiny size on whatever "
                         "device JAX has (the CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "run on a TPU host, or pass --rehearse for the tiny-size "
              "rehearsal", file=sys.stderr)
        return 2
    size = REHEARSAL if args.rehearse else CHIP
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache_dir}")
    print("device time: not measured (correctness smoke)")

    check_da_backends(size, args.seed)
    cfg = model_config(args.rehearse)
    check_paged_attention(cfg, size, args.seed)
    serve(cfg, size, args.seed, args.rehearse)

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print("peak bytes in use: "
          + (f"{peak} ({peak / 2**30:.2f} GiB)" if peak is not None
             else "not reported by this backend"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Autotune the DA engine's shape-aware dispatch: time every registered
backend on one representative shape per (M, K·N) bucket and write the JSON
cost cache that ``mode="auto"`` loads at dispatch time.

    PYTHONPATH=src python benchmarks/engine_autotune.py            # full
    PYTHONPATH=src python benchmarks/engine_autotune.py --quick    # smaller reps
    PYTHONPATH=src python benchmarks/engine_autotune.py --x-bits 8 4

The cache (default ``artifacts/engine_autotune.json``, override with
``REPRO_ENGINE_AUTOTUNE``) maps shape buckets to measured µs per backend::

    {"table": {"dec:s:b8": {"lut": 120.4, "bitplane_stacked": 88.1, ...}}}

Only *eligible* backends are timed (LUT modes are skipped when the bucket's
LUT blow-up would exceed ``--lut-cell-limit`` — the same bound the serving
freeze applies), and the Pallas kernels are skipped on CPU, where interpret
mode is a correctness tool rather than a fast path.  Measurements are taken
on whatever ``jax.default_backend()`` this runs on; the cache records the
device so a CPU-tuned table is not silently trusted on TPU.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.da import DAConfig
from repro.core.engine import (
    BUCKET_SHAPES,
    DEFAULT_LUT_LIMIT,
    default_cache_path,
    jit_backend,
    lut_cells,
    pack_quantized,
    registry_fingerprint,
    set_cost_table,
    shape_bucket,
    timeable_backends,
)

try:
    from stamp import bench_stamp
except ImportError:  # running as a module from the repo root
    from benchmarks.stamp import bench_stamp

# Shrunk representatives for --quick (CI / CPU smoke): same buckets, less work.
QUICK_SHAPES = {
    "dec:s": (4, 64, 128),
    "dec:m": (4, 256, 512),
    "dec:l": (4, 1024, 1536),
    "mid:s": (32, 64, 128),
    "mid:m": (32, 256, 512),
    "mid:l": (32, 1024, 1536),
    "big:s": (384, 64, 128),
    "big:m": (384, 256, 512),
    "big:l": (384, 1024, 1536),
}


def time_backend(fn, *args, iters: int = 3) -> float:
    """Median wall-time in µs over ``iters`` timed calls (after warm-up)."""
    jax.block_until_ready(fn(*args))  # compile + warm caches
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def tune(
    x_bits_list, group_size: int, lut_cell_limit: int, quick: bool, iters: int
) -> tuple:
    """Time every eligible backend per bucket: ``(table, failures)``, where
    ``failures`` lists the ``(bucket, backend)`` pairs that raised."""
    rng = np.random.default_rng(0)
    shapes = QUICK_SHAPES if quick else BUCKET_SHAPES
    table: dict = {}
    failures: list = []
    for x_bits in x_bits_list:
        cfg = DAConfig(group_size=group_size, x_bits=x_bits, x_signed=True)
        for cell, (m, k, n) in shapes.items():
            bucket = shape_bucket(m, k, n, x_bits)
            with_luts = lut_cells(k, n, group_size) <= lut_cell_limit
            w = rng.integers(-128, 128, (k, n)).astype(np.int32)
            lo = 1 << (x_bits - 1)
            x = rng.integers(-lo, lo, (m, k)).astype(np.int32)
            packed = pack_quantized(w, cfg=cfg, with_luts=with_luts)
            xj = jnp.asarray(x)
            costs = {}
            for spec in timeable_backends(cfg, packed.has_luts):
                fn = jit_backend(spec, cfg)
                try:
                    costs[spec.name] = round(
                        time_backend(fn, xj, packed, iters=iters), 1)
                except Exception as e:  # noqa: BLE001 — record, keep tuning
                    print(f"  {bucket} {spec.name}: failed ({e})")
                    failures.append((bucket, spec.name))
            table[bucket] = costs
            best = min(costs, key=costs.get) if costs else "-"
            pretty = ", ".join(f"{b}={us:.0f}us" for b, us in costs.items())
            print(f"{bucket:12s} ({m}x{k}x{n}): {pretty}  -> {best}")
    return table, failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller representative shapes (CI / CPU smoke)")
    ap.add_argument("--x-bits", type=int, nargs="+", default=[8],
                    help="input bit widths to tune (e.g. --x-bits 8 4)")
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--lut-cell-limit", type=int, default=DEFAULT_LUT_LIMIT,
                    help="max LUT cells per matrix before LUT modes are "
                         "skipped (default: the serving freeze's bound, so "
                         "every bucket the freeze gives LUTs gets them timed)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="cache path (default: engine default_cache_path())")
    args = ap.parse_args()

    table, failures = tune(args.x_bits, args.group_size, args.lut_cell_limit,
                           args.quick, args.iters)
    if failures:
        # a table missing a backend that failed would silently rank it out
        raise SystemExit(
            "engine_autotune: backends failed to compile or run: "
            + ", ".join(f"{name} ({bucket})" for bucket, name in failures)
            + "; cache not written")
    out = args.out or default_cache_path()
    payload = {
        # provenance stamp (git sha, seed, device, interpret flag, schema
        # version) rides the cache like every other benchmark artifact; the
        # explicit keys below win on collision so the loader contract
        # ("version"/"registry"/"table") is unchanged
        **bench_stamp(),
        "version": 1,
        "device": jax.default_backend(),
        # Stamp the backend registry this cache was tuned against: a loader
        # seeing a different fingerprint warns and falls back to the
        # heuristic instead of trusting stale rankings (or KeyError-ing on
        # renamed backends).
        "registry": registry_fingerprint(),
        "group_size": args.group_size,
        "quick": args.quick,
        "table": table,
    }
    import pathlib

    p = pathlib.Path(out)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=1, sort_keys=True))
    set_cost_table(table)  # make this process dispatch on fresh numbers too
    print(f"\nwrote {p} ({len(table)} buckets, device={payload['device']})")


if __name__ == "__main__":
    main()

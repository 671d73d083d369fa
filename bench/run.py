"""Run one benchmark cell of the DA serving stack on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, in order:

1. set-up (``setup_s``): the configuration's float weights are drawn from
   the seed on the device in one jitted call, ``ServeEngine`` freezes them
   into DA artifacts, ``warmup()`` compiles (or loads from the persistent
   cache in ``<checkout>/.jax_cache``) every step shape, and the traffic is
   made from the seed;
2. the window: the mix is driven through ``ServeEngine.submit()`` /
   ``step()`` for ``--seconds`` (``--trace 1``: at most TRACE_WINDOW_S,
   under the profiler);
3. ``memory_peak_bytes`` is read, the engine is freed, and the served
   tokens of a sample of finished requests are compared with the plain
   float32 reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit.
Without a TPU (or with fewer chips than the cell asks for) it exits 3 and
prints no result. ``--rehearse`` runs the same path on the CPU at a tiny
size, with kernels interpreted; its output names the CPU and carries no
device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))

#: longest traced window: the capture of a few thousand steps
TRACE_WINDOW_S = 10.0
CACHE_DIR = REPO / ".jax_cache"
TRACE_DIR = REPO / ".bench_trace"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size (no device metrics)")
    return ap.parse_args(argv)


def _compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, given to
    the program through the variable its entry points read."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()


def _err(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = _args(argv)
    _compile_cache()
    import jax

    from bench import catalog

    bench = catalog.benchmark()
    cell = catalog.cell(bench, args.workload)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        _err(f"JAX found no TPU (platform {dev.platform!r}); the benchmark "
             "runs on the chip (--rehearse: CPU rehearsal)")
        return 3
    if not args.rehearse and len(devices) < int(cell["chips"]):
        _err(f"cell {cell['name']} asks for {cell['chips']} chips; JAX "
             f"found {len(devices)}")
        return 3
    return run(args, bench, cell, dev, devices)


def run(args, bench, cell, dev, devices) -> int:
    import jax

    from bench import catalog, check, drive, readings, sut, tracing, weights
    from bench.peaks import peaks_for
    from bench.traffic import Traffic, scale_lengths

    model = catalog.config(cell["config"])
    serving = dict(model["serving"])
    mix = catalog.traffic(cell["traffic"])
    limits = catalog.limits(cell["name"])
    if args.rehearse:
        model, serving = sut.rehearsal_model(model, serving)
        mix = scale_lengths(mix, serving["max_len"] / 1024)
    peaks = None if args.rehearse else peaks_for(dev.device_kind)
    tracing_on = bool(args.trace)

    # -- set-up --------------------------------------------------------------
    cfg = sut.model_config(model)
    params = jax.block_until_ready(weights.make_params(args.seed, model))
    eng = sut.build_engine(cfg, params, serving, trace=tracing_on)
    del params  # only the frozen artifact is served
    eng.warmup()
    traffic = Traffic(mix, args.seed, model["vocab_size"])
    traffic.spec(0)
    setup_s = time.perf_counter() - T_START
    _err(f"set-up {setup_s:.3f}s (cell {cell['name']}, seed {args.seed}, "
         f"device {dev.device_kind} x{len(devices)})")

    # -- the window ----------------------------------------------------------
    window_s = min(args.seconds, TRACE_WINDOW_S) if tracing_on \
        else args.seconds
    trace_dir = TRACE_DIR / f"{cell['name']}-{args.seed}"

    def on_start():
        if tracing_on:
            shutil.rmtree(trace_dir, ignore_errors=True)
            eng.obs.tracer.drain()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    def on_end():
        if tracing_on:
            jax.profiler.stop_trace()

    batch = int(serving["batch"])
    arrival = mix["arrival"]
    if arrival["kind"] == "backlog":
        win = drive.backlog(eng, traffic, batch,
                            int(arrival["depth_per_lane"]) * batch, window_s,
                            sut, on_start, on_end, tracing_on)
    else:
        win = drive.poisson(eng, traffic, float(mix.get("warm_in_s", 0)),
                            window_s, sut, on_start, on_end, tracing_on)
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")

    # -- what the traced window holds ----------------------------------------
    view = readings.RunView(model, serving, peaks, (win.start, win.end),
                            [t for t in win.ticks
                             if win.start <= t[0] < win.end])
    breakdown = None
    if tracing_on:
        prompt_len = {u: s.prompt_len for u, s in win.served.items()}
        view.calls = sut.calls_from_events(
            list(eng.obs.tracer.events), prompt_len, view.window,
            eng._rt.prefill_lanes)
        if eng.obs.tracer.dropped:
            _err(f"engine trace dropped {eng.obs.tracer.dropped} events; "
                 "work counts are incomplete and are not reported")
            view.calls = None
        if not args.rehearse:
            view.capture, breakdown = _reduce_trace(
                eng, trace_dir, view, sut, tracing)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- free the program, then the reference --------------------------------
    del eng
    gc.collect()
    uids = check.choose(win, check.finished(win), args.seed)
    samples = [(win.served[u].req.prompt, list(win.served[u].req.generated))
               for u in uids]
    t_ref = time.perf_counter()
    gaps = check.reference_gaps(samples, model, args.seed,
                                int(serving["max_len"]))
    ref_s = time.perf_counter() - t_ref
    failed = 0 if win.drained else sum(
        1 for u in win.attempted if len(win.served[u].req.generated)
        < win.served[u].req.max_new_tokens)
    bad = check.bad_requests(win, model["vocab_size"])
    # the rehearsal's tiny model has a limit of its own, read on the CPU
    limit = limits.get("max_logit_gap", {}).get(
        "rehearsal_limit" if args.rehearse else "limit")
    correct, checks = check.judge(gaps, bad, failed, limit)

    # -- the result ----------------------------------------------------------
    metrics = {}
    if args.rehearse:
        pass  # a CPU run carries no device metric
    elif tracing_on:
        for m in catalog.metrics_of(bench, "per_layer", cell["name"]):
            value = catalog.reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = readings.end_to_end(win, setup_s)
        for m in catalog.metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if view.capture is not None:
        lo, hi = view.capture.window
        device["busy_s"] = view.busy_seconds()
        device["window_s"] = hi - lo
    out = {"correct": bool(correct), "attempted": len(win.attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.rehearse:
        out["rehearsal"] = {"ticks": len(view.ticks),
                            "calls": len(view.calls or [])}
    if win.late_s:
        out["generator_late_ms"] = {
            "p50": 1e3 * readings.percentile(win.late_s, 50),
            "max": 1e3 * max(win.late_s)}
    out["reference_s"] = ref_s
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def _reduce_trace(eng, trace_dir, view, sut, tracing):
    """Read the capture, attach each step op's named scope, and reduce."""
    paths = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not paths:
        _err("the profiler wrote no capture")
        return None, None
    ops, modules, spans = tracing.read_xplane(str(paths[-1]))
    ticks = sorted((s for s in spans if s[0] == "bench.tick"),
                   key=lambda s: s[1])
    if not ticks:
        _err("the capture holds no tick of the window")
        return None, None
    shapes = sorted({tracing.step_shape(s[0]) for s in spans
                     if tracing.step_shape(s[0])})
    hlo = {shape: tracing.hlo_op_names(sut.step_hlo(eng, shape))
           for shape in shapes}
    cap = tracing.build(ops, modules, spans, (ticks[0][1], ticks[-1][2]),
                        hlo)
    return cap, tracing.breakdown(cap)


if __name__ == "__main__":
    sys.exit(main())

"""Find the highest arrival rate a configuration sustains, once.

    python bench/sweep.py --config qwen3-8b --traffic chat-backlog \
        --rates 1,1.5,2,2.5 --seconds 30 --seed 7

One process: the engine is set up once and warmed, then for each rate, in
ascending order, the mix's lengths are driven open-loop (``drive.poisson``)
at that rate, the engine drained, and one JSON line printed: requests due
in the window, the queue left at its close, TTFT and inter-token
percentiles, and the TTFT median of the window's first and second halves.
A rate is sustained when the queue does not grow through the window (the
second half's TTFT median stays within 1.5x of the first's and the queue
at the close holds fewer requests than one lane-load). An open-loop cell's rate
is then written into its traffic file by hand, at 0.8 x the highest
sustained rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))

#: requests due before the window are served and not counted
WARM_IN_S = 5.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True,
                    help="mix whose lengths are offered (its own arrival "
                         "process is replaced by Poisson at each rate)")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from bench.run import _compile_cache

    _compile_cache()
    import jax

    from bench import catalog, drive, readings, sut, weights
    from bench.traffic import Traffic, scale_lengths

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 3
    model = catalog.config(args.config)
    serving = dict(model["serving"])
    mix = catalog.traffic(args.traffic)
    if args.rehearse:
        model, serving = sut.rehearsal_model(model, serving)
        mix = scale_lengths(mix, serving["max_len"] / 1024)
    params = jax.block_until_ready(weights.make_params(args.seed, model))
    eng = sut.build_engine(sut.model_config(model), params, serving, False)
    del params
    eng.warmup()
    noop = lambda: None  # noqa: E731
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrival={"kind": "poisson", "rate_per_s": rate})
        win = drive.poisson(eng, Traffic(m, args.seed, model["vocab_size"]),
                            WARM_IN_S, args.seconds,
                            sut, noop, noop, False)
        queued_at_close = sum(
            1 for u in win.attempted
            if win.served[u].req.first_token_t is None
            or win.served[u].req.first_token_t > win.end)
        half = win.start + 0.5 * (win.end - win.start)
        ttft = {u: win.served[u].req.first_token_t - win.served[u].due
                for u in win.attempted
                if win.served[u].req.first_token_t is not None}
        first = [v for u, v in ttft.items() if win.served[u].due < half]
        second = [v for u, v in ttft.items() if win.served[u].due >= half]
        e2e = readings.end_to_end(win, 0.0)
        row = {"rate_per_s": rate, "due": len(win.attempted),
               "drained": win.drained, "queued_at_close": queued_at_close,
               "ttft_p95_ms": e2e.get("ttft_p95_ms"),
               "itl_p95_ms": e2e.get("itl_p95_ms"),
               "ttft_p50_first_half_ms":
                   1e3 * statistics.median(first) if first else None,
               "ttft_p50_second_half_ms":
                   1e3 * statistics.median(second) if second else None,
               "tokens_per_s": e2e["out_tokens_per_s"]}
        row["sustained"] = bool(
            win.drained and first and second
            and row["ttft_p50_second_half_ms"]
            <= 1.5 * row["ttft_p50_first_half_ms"]
            and queued_at_close < int(serving["batch"]))
        print(json.dumps(row), flush=True)
        while eng.step() or eng.queue:
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

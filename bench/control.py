"""Readings that set a cell's ``correct`` limit, on the chip.

    python bench/control.py --workload <cell> --seeds 11,12,... \
        --seconds 30 --out program.jsonl
    python bench/control.py --workload <cell> --seeds 11,12,13 \
        --x-bits 4 --seconds 30 --out control.jsonl

For every seed, one whole run of the cell through ``bench/run.py``'s own
path (set-up, the window at the cell's load, the sample of finished
requests, the float32 reference, ``check.judge``), in this one process.

* Without ``--x-bits`` it is the program as the benchmark runs it: its
  ``max_logit_gap`` readings are the lower reading of the limit.
* With ``--x-bits 4`` it is the control: the program's own lower-precision
  path, ``core.engine.x_bits_override`` — every DA product evaluates only
  the top 4 of its 8 activation bit-planes (int4 for the stated int8) —
  switched on around the whole run, so the control serves and decodes its
  own tokens and the harness judges them as it judges the program's. Its
  readings are the upper reading, and ``correct`` has to come out false.

The override is read when a step is traced, and every engine traces its
own step, so one precision per process is all this relies on. One JSON
line per seed goes to standard output and to ``--out``. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def one_run(cell: str, seed: int, seconds: float, x_bits=None,
            rehearse: bool = False):
    """The last line of one ``bench/run.py`` run, as a dict, with the
    program's activation bit-planes cut to ``x_bits`` where given."""
    from bench import run
    from repro.core.engine import x_bits_override

    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"] + (["--rehearse"] if rehearse
                                            else [])
    out = io.StringIO()
    with x_bits_override(x_bits), contextlib.redirect_stdout(out):
        rc = run.main(argv)
    if rc != 0:
        raise RuntimeError(f"bench/run.py exited {rc} for seed {seed}")
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--x-bits", type=int, default=None,
                    help="activation bit-planes of the control (none: the "
                         "program as it is served)")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = one_run(args.workload, seed, args.seconds, args.x_bits,
                      args.rehearse)
        gap = res["checks"]["max_logit_gap"]
        row = {"cell": args.workload, "seed": seed, "x_bits": args.x_bits,
               "correct": res["correct"], "max_logit_gap": gap["value"],
               "limit": gap["limit"],
               "sampled_tokens": res["checks"]["sampled_tokens"]["value"],
               "attempted": res["attempted"], "failed": res["failed"],
               "seconds": time.perf_counter() - t0}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Spread of a cell's metrics over sets of runs, as the bounds are set.

    python bench/spread.py run1.json run2.json ... --sets 2

Each file holds a run's standard output (its last line is the result). The
runs are split into ``--sets`` consecutive sets; for each metric, each set's
spread is the distance between its first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of its median. Prints
the sets' medians and spreads and the wider spread.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--sets", type=int, default=2)
    args = ap.parse_args(argv)
    runs = []
    for path in args.files:
        with open(path) as f:
            runs.append(json.loads(f.read().strip().splitlines()[-1]))
    size = len(runs) // args.sets
    sets = [runs[i * size:(i + 1) * size] for i in range(args.sets)]
    for name in sorted({m for r in runs for m in r["metrics"]}):
        per = [[r["metrics"][name]["value"] for r in s] for s in sets]
        meds = [statistics.median(v) for v in per]
        spreads = [spread(v) for v in per]
        print(json.dumps({"metric": name, "medians": meds,
                          "spreads": spreads, "widest": max(spreads),
                          "values": per}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

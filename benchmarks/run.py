# One function per paper table/figure. Prints ``name,us_per_call,derived``-
# style CSV blocks per benchmark.
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        da_model_scale,
        lenet_conv1,
        roofline_table,
        scaling,
        table1_comparison,
    )

    benches = [
        ("table1_comparison (paper Table I)", table1_comparison.main),
        ("scaling (paper Fig. 5)", scaling.main),
        ("lenet_conv1 (paper Fig. 3, §III-C)", lenet_conv1.main),
        ("da_model_scale (beyond-paper)", da_model_scale.main),
        ("roofline_table (§Roofline)", roofline_table.main),
    ]
    failures = 0
    for name, fn in benches:
        print(f"\n===== {name} =====")
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Share of the traced window (%) in which no operation ran on the chip:
100 x (1 - union of XLA op intervals / window)."""


def read(run):
    if run.capture is None or not run.capture.ops:
        return None
    lo, hi = run.capture.window
    return 100.0 * (1.0 - run.busy_seconds() / (hi - lo))

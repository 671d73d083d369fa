"""Mean host wall time of one ServeEngine.step() in the window (ms), from
the benchmark's own span around each call. Backlog cells."""


def read(run):
    if not run.ticks:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in run.ticks) / len(run.ticks)

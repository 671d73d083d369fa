"""DA VMM share of its roofline (%): the least time of every DA product of
the window (larger of 2·M·K·N / int8 peak and codes + scales + x + y bytes
/ HBM bandwidth, per product) over the summed device time of ops under
``da_*`` named scopes."""

from bench import tracing


def read(run):
    if run.capture is None or not run.calls:
        return None
    spent = run.device_seconds(tracing.is_da)
    least = run.da_least_seconds()
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent

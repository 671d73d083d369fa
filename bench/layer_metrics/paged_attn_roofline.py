"""Fused paged-attention share of its roofline (%): the least time of every
read of the window (larger of 4·T·ctx·heads·hd flops / bf16 peak and the
live K/V pages + q + out bytes / HBM bandwidth, per call and layer) over
the summed device time of ``paged_attn_fused_*`` ops."""

from bench import tracing


def read(run):
    if run.capture is None or not run.calls:
        return None
    spent = run.device_seconds(tracing.is_paged_attn)
    least = run.attn_least_seconds()
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent

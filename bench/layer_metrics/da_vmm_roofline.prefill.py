"""DA VMM share of its roofline on the prefill calls alone (%): the least
time of every DA product of the window's ``prefill`` calls (LM head rows
included; larger of 2·M·K·N / int8 peak and codes + scales + x + y bytes /
HBM bandwidth, per product) over the device time of ops under ``da_*``
named scopes in the modules those calls dispatched.

A module's call is found by time, not by shape (``[4x1]`` is both a decode
width and the one-token prefill bucket): each step module is paired with
its ``paged_step[RxT]`` dispatch span (``tracing.match_modules``), and that
span with the engine's ``Call`` that starts nearest to it. The engine
stamps its calls on its own clock and the capture its spans on the
profiler's; calls and dispatches come in the same order, so the offset
between the two clocks is the median gap between them, taken under the
pairing in order that agrees best (as ``match_modules`` aligns device and
host). The jitter of that gap (the end of the call's packing) is far below
the time between two calls."""

import bisect
import dataclasses
import statistics

from bench import tracing


def _dispatch_spans(cap):
    return sorted((i for i, s in enumerate(cap.spans)
                   if tracing.step_shape(s[0])), key=lambda i: cap.spans[i][1])


def _clock_offset(starts, calls):
    """Profiler clock minus engine clock: the median gap under the pairing
    in order (skipping up to two leading spans or calls) whose gaps agree
    best."""
    best = None
    for skip_s in range(min(3, len(starts))):
        for skip_c in range(min(3, len(calls))):
            n = min(len(starts) - skip_s, len(calls) - skip_c)
            if n <= 0:
                continue
            diffs = [starts[skip_s + k] - calls[skip_c + k].t0
                     for k in range(n)]
            med = statistics.median(diffs)
            err = statistics.median(abs(d - med) for d in diffs)
            if best is None or err < best[0] - 1e-5:  # 10 us: clock noise
                best = (err, med)
    return None if best is None else best[1]


def module_kinds(run):
    """module index -> the kind ("prefill" | "decode") of the engine call
    that dispatched it, for every step module paired with a call."""
    cap = run.capture
    spans = _dispatch_spans(cap)
    calls = sorted(run.calls, key=lambda c: c.t0)
    offset = _clock_offset([cap.spans[i][1] for i in spans], calls)
    if offset is None:
        return {}
    t0s = [c.t0 + offset for c in calls]
    kind_of_span = {}
    for i in spans:
        name, start, _ = cap.spans[i]
        j = bisect.bisect_left(t0s, start)
        near = [k for k in (j - 1, j) if 0 <= k < len(calls)]
        k = min(near, key=lambda k: abs(t0s[k] - start))
        if calls[k].shape == tracing.step_shape(name):
            kind_of_span[i] = calls[k].kind
    pairs, _ = tracing.match_modules(cap.modules, cap.spans)
    return {m: kind_of_span[s] for m, s in pairs if s in kind_of_span}


def da_seconds_by_kind(run):
    """Device time of ``da_*``-scoped ops in the window, by the kind of the
    call whose module ran them (ops in no paired module: ``None``)."""
    kinds = module_kinds(run)
    out = {}
    for kind in ("prefill", "decode", None):
        out[kind] = run.device_seconds(
            lambda o, k=kind: tracing.is_da(o) and kinds.get(o.module) == k)
    return out


def read(run):
    if run.capture is None or not run.calls:
        return None
    spent = da_seconds_by_kind(run)["prefill"]
    prefill = [c for c in run.calls if c.kind == "prefill"]
    least = dataclasses.replace(run, calls=prefill).da_least_seconds()
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent

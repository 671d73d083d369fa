"""Reduction of a profiler capture to device intervals and host spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but JAX's
``ProfileData``. On a TPU the device plane ``/device:TPU:<i>`` carries an
``XLA Ops`` line (one event per executed HLO instruction, named by its HLO
text) and an ``XLA Modules`` line (one event per executable run). Host
``TraceAnnotation`` spans sit on the ``/host:CPU`` plane.

* busy: the union of ``XLA Ops`` intervals inside the window; idle is the
  rest of the window.
* scope attribution: an op event names its HLO instruction (``%fusion.51 =
  ...``). The instruction's ``op_name`` metadata (the ``jax.named_scope``
  path, e.g. ``.../da_bitplane_stacked/dot_general``) comes from the
  compiled module's HLO text, matched to the module the op ran in.
* clocks: device timestamps are shifted onto the host clock by the median
  offset between each step module's start and the host span that
  dispatched it.
* idle gaps are labelled with the innermost host span open at their middle.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds, [start, end)

_INSTR = re.compile(r"^\s*%?([A-Za-z0-9_.\-]+)\s*=")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=.*?metadata=\{[^}]*?"
    r"op_name=\"([^\"]*)\"")
_DA_SCOPE = re.compile(r"(?:^|/)da_[A-Za-z0-9_]+(?:/|$)")


@dataclasses.dataclass
class Op:
    instr: str              # HLO instruction name, e.g. "fusion.51"
    start: float            # seconds, host clock after alignment
    dur: float
    module: Optional[int] = None  # index into Capture.modules
    op_name: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Capture:
    """What one traced window holds, on the host clock (seconds)."""

    ops: List[Op]
    modules: List[Tuple[str, float, float]]   # (name, start, end)
    spans: List[Tuple[str, float, float]]     # host spans (name, start, end)
    window: Interval


def instr_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """instruction name -> op_name metadata, from a module's HLO text."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


def is_da(op: Op) -> bool:
    return bool(_DA_SCOPE.search(op.op_name))


def is_paged_attn(op: Op) -> bool:
    return ("paged_attn_fused_" in op.op_name
            or op.instr.startswith("paged_attn_fused_"))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(ops: Sequence[Op], window: Interval) -> float:
    return sum(e - s for s, e in union(clip(((o.start, o.end) for o in ops),
                                            window)))


def idle_gaps(ops: Sequence[Op], window: Interval) -> List[Interval]:
    busy = union(clip(((o.start, o.end) for o in ops), window))
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def read_xplane(path: str, device: int = 0):
    """Raw events: (ops, modules, host spans), each on its own clock, in
    seconds. Host spans are kept when their name passes ``keep_span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Op(instr_name(ev.name), ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9) for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = [(ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if keep_span(ev.name):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return ops, modules, spans


def keep_span(name: str) -> bool:
    return name.startswith("bench.") or name.startswith("paged_step[")


def step_shape(span_name: str) -> Optional[Tuple[int, int]]:
    m = re.match(r"paged_step\[(\d+)x(\d+)\]", span_name)
    return (int(m.group(1)), int(m.group(2))) if m else None


def match_modules(modules, spans, prefix: str = "jit_counted"):
    """Pair each run of the step executable with the host span that
    dispatched it, in order. Returns (pairs [(module_index, span_index)],
    device-to-host offset in seconds)."""
    mods = [i for i, m in enumerate(modules) if m[0].startswith(prefix)]
    steps = [i for i, s in enumerate(spans) if step_shape(s[0])]
    steps.sort(key=lambda i: spans[i][1])
    mods.sort(key=lambda i: modules[i][1])
    if not mods or not steps:
        return [], 0.0
    # a module dispatched before the capture began has no span: the first
    # span's module is the one whose start is nearest after that span's
    # start, under any offset below the gap to the next module
    best, best_err = 0, None
    for skip in range(min(3, len(mods))):
        n = min(len(mods) - skip, len(steps))
        diffs = [modules[mods[skip + k]][1] - spans[steps[k]][1]
                 for k in range(n)]
        if not diffs:
            continue
        med = statistics.median(diffs)
        err = statistics.median(abs(d - med) for d in diffs)
        if best_err is None or err < best_err - 1e-5:  # 10 us: clock noise
            best, best_err = skip, err
    n = min(len(mods) - best, len(steps))
    pairs = [(mods[best + k], steps[k]) for k in range(n)]
    offset = statistics.median(modules[m][1] - spans[s][1] for m, s in pairs)
    return pairs, offset


def build(ops, modules, spans, window: Interval,
          hlo_by_shape: Dict[Tuple[int, int], Dict[str, str]],
          prefix: str = "jit_counted") -> Capture:
    """Align device events to the host clock, assign each op to the module
    it ran in, and attach op_name metadata from that module's HLO."""
    pairs, offset = match_modules(modules, spans, prefix)
    shape_of: Dict[int, Tuple[int, int]] = {
        m: step_shape(spans[s][0]) for m, s in pairs}
    mods = [(name, s - offset, e - offset) for name, s, e in modules]
    order = sorted(range(len(mods)), key=lambda i: mods[i][1])
    aligned = []
    j = 0
    for op in sorted(ops, key=lambda o: o.start):
        o = Op(op.instr, op.start - offset, op.dur)
        while j + 1 < len(order) and mods[order[j + 1]][1] <= o.start:
            j += 1
        if order and mods[order[j]][1] <= o.start < mods[order[j]][2]:
            o.module = order[j]
            shape = shape_of.get(o.module)
            names = hlo_by_shape.get(shape) if shape else None
            if names:
                o.op_name = names.get(o.instr, "")
        aligned.append(o)
    return Capture(aligned, mods, spans, window)


def label_gap(gap: Interval, spans) -> str:
    """Innermost host span open at the middle of an idle gap."""
    mid = 0.5 * (gap[0] + gap[1])
    inner = None
    for name, s, e in spans:
        if s <= mid < e and (inner is None or e - s < inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0] if inner else "no host span"


def op_label(op: Op) -> str:
    """A stable group name for the breakdown: the last two named-scope
    components of op_name, else the instruction name without its number."""
    if op.op_name:
        parts = [p for p in op.op_name.split("/")
                 if p and not p.startswith("jit(") and p not in
                 ("while", "body", "closed_call", "cond")]
        if parts:
            return "/".join(parts[-2:])
    return re.sub(r"\.\d+$", "", op.instr)


def breakdown(cap: Capture, top: int = 10):
    """The device ops that took most time and the idle time by host
    activity, each as [[name, seconds], ...], longest first."""
    by_op: Dict[str, float] = {}
    for o in cap.ops:
        for s, e in clip([(o.start, o.end)], cap.window):
            by_op[op_label(o)] = by_op.get(op_label(o), 0.0) + (e - s)
    by_gap: Dict[str, float] = {}
    for g in idle_gaps(cap.ops, cap.window):
        lab = label_gap(g, cap.spans)
        by_gap[lab] = by_gap.get(lab, 0.0) + (g[1] - g[0])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def scoped_seconds(cap: Capture, pred) -> float:
    """Summed device time, inside the window, of ops that satisfy pred."""
    total = 0.0
    for o in cap.ops:
        if pred(o):
            for s, e in clip([(o.start, o.end)], cap.window):
                total += e - s
    return total

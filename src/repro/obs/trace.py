"""Structured request/scheduler tracing for the paged serving runtime.

A :class:`TraceRecorder` is a bounded ring buffer of host-side events — the
per-request lifecycle (submit → admit → prefill-chunk × N → decode-tick × M
→ preempt/re-admit → spec rounds → finish) and the per-tick scheduler story
(batch shape bucket, lanes, pages allocated/COW'd/evicted).  Events carry
``perf_counter`` timestamps, the SAME clock the latency metrics use, so a
trace reconstructs TTFT/ITL exactly (the token events are stamped with the
very ``now`` the scheduler put into ``Request.token_times``).

Events export to Chrome ``trace_event`` JSON (``repro.obs.export``) and load
in Perfetto / ``chrome://tracing``: each request is a named track, spans
nest by B/E pairing, scheduler ticks are complete ("X") events with the
shape/page args attached.

Tracing is OFF by default (``TraceRecorder(enabled=False)`` is a no-op whose
every method is one attribute test) and must never perturb decode — token
bit-identity with tracing on/off is test-asserted.  The ring buffer bounds
memory on long serves: the newest ``capacity`` events win, and
:meth:`span_balance` is computed from lifetime depth counters, not the
buffer, so balance checks survive wraparound.

:meth:`TraceRecorder.phase` is the one way to open a span that a profiler
capture should see: it records the span twice over the same interval, as a
``jax.profiler.TraceAnnotation`` on the profiler's clock (so the device
timeline lines up with what the host was doing) and as an ``"X"`` event on
the recorder's.  The scheduler opens one per host phase of its tick
(``sched.*``) and one per device dispatch (``paged_step[RxT]``, …).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

import jax

#: Track name for scheduler-level (per-tick) events.
SCHED_TRACK = "scheduler"


def request_track(uid: int) -> str:
    return f"req:{uid}"


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One structured event.

    ``ph`` follows the Chrome trace_event phases this recorder emits:
    ``"B"``/``"E"`` span begin/end, ``"X"`` complete (carries ``dur``),
    ``"i"`` instant.  ``ts``/``dur`` are seconds on the perf_counter clock
    (export converts to microseconds).
    """

    name: str
    ph: str
    ts: float
    track: str
    dur: float = 0.0
    args: Optional[Dict[str, Any]] = None


class TraceRecorder:
    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.enabled = enabled
        self.events: deque = deque(maxlen=capacity)
        # lifetime span-depth ledger per track: +1 on begin, -1 on end.
        # Balance is judged on these, not the ring buffer, so an evicted
        # "B" event cannot fake an unbalanced trace.
        self._depth: Dict[str, int] = {}
        self.dropped = 0
        self._t0 = time.perf_counter()

    # -- emission ------------------------------------------------------------
    def _push(self, ev: TraceEvent) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append(ev)

    def begin(self, name: str, track: str, ts: Optional[float] = None,
              **args) -> None:
        if not self.enabled:
            return
        self._depth[track] = self._depth.get(track, 0) + 1
        self._push(TraceEvent(name, "B", self._now(ts), track,
                              args=args or None))

    def end(self, name: str, track: str, ts: Optional[float] = None,
            **args) -> None:
        if not self.enabled:
            return
        self._depth[track] = self._depth.get(track, 0) - 1
        self._push(TraceEvent(name, "E", self._now(ts), track,
                              args=args or None))

    def complete(self, name: str, track: str, t_start: float,
                 dur: float, **args) -> None:
        """One already-finished span (per-tick phases: start time + duration
        measured by the caller)."""
        if not self.enabled:
            return
        self._push(TraceEvent(name, "X", t_start, track, dur=dur,
                              args=args or None))

    def instant(self, name: str, track: str, ts: Optional[float] = None,
                **args) -> None:
        if not self.enabled:
            return
        self._push(TraceEvent(name, "i", self._now(ts), track,
                              args=args or None))

    @contextlib.contextmanager
    def span(self, name: str, track: str, **args) -> Iterator[None]:
        """B/E pair guarded by try/finally — a span opened is a span closed
        even when the body raises (the balance invariant the tests assert)."""
        self.begin(name, track, **args)
        try:
            yield
        finally:
            self.end(name, track)

    def phase(self, name: str, track: str = SCHED_TRACK, **args):
        """A span on both clocks: ``jax.profiler.TraceAnnotation(name)``
        for a profiler capture and an ``"X"`` event on ``track``, bracketing
        the same interval.  Disabled, it returns one shared null context (one
        attribute test, nothing recorded, no annotation entered)."""
        if not self.enabled:
            return _OFF
        return _Phase(self, name, track, args)

    def _now(self, ts: Optional[float]) -> float:
        return time.perf_counter() if ts is None else ts

    # -- inspection ----------------------------------------------------------
    def span_balance(self) -> Dict[str, int]:
        """Track → currently-open span depth (every value should be 0 once
        serving drains; nonzero means a begin without its end)."""
        return {t: d for t, d in self._depth.items() if d != 0}

    def drain(self) -> List[TraceEvent]:
        out = list(self.events)
        self.events.clear()
        return out

    def __len__(self) -> int:
        return len(self.events)


class _Phase:
    """One open phase: a TraceAnnotation and an "X" event over the same
    interval.  Entering yields the args dict, so a caller may add args it
    only learns inside the span."""

    __slots__ = ("_rec", "_name", "_track", "_args", "_ann", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, track: str,
                 args: Dict[str, Any]):
        self._rec, self._name, self._track, self._args = rec, name, track, args

    def __enter__(self) -> Dict[str, Any]:
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self._args

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._rec.complete(self._name, self._track, self._t0, t1 - self._t0,
                           **self._args)
        return False


class _ArgSink:
    """What a disabled phase yields: takes args and keeps none."""

    __slots__ = ()

    def update(self, **args) -> None:
        pass


#: the context every phase of a disabled recorder shares: no object is
#: built per call, and nothing reaches the profiler
_OFF = contextlib.nullcontext(_ArgSink())

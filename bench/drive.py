"""Drives the engine with a traffic mix and times what users see.

Every timestamp is ``time.perf_counter()``, the clock the engine stamps
``Request.token_times`` with. Two arrival processes:

* backlog: the queue is kept ``depth_per_lane x batch`` deep; the window
  opens after the first tick that finds every lane occupied.
* poisson: an open loop. Request i is due at ``start + arrival_s`` and is
  submitted at the first loop turn after that; it is timed from when it was
  due, so a stall delays the requests behind it. Requests due in the
  ``warm_in_s`` before the window are served and not counted. After the
  window closes no request is submitted, and the engine runs until every
  request due in the window has finished, or a minute has passed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Served:
    """One request as offered and as served."""

    req: object          # the engine's Request
    prompt_len: int
    due: Optional[float]  # poisson: when it was due; backlog: None
    submitted: float


@dataclasses.dataclass
class Window:
    start: float
    end: float
    ticks: List[Tuple[float, float]]      # (t0, t1) of each tick in window
    served: Dict[int, Served]
    attempted: List[int]                  # uids the window is judged on
    late_s: List[float]                   # generator lateness (poisson)
    drained: bool = True


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _submit(eng, traffic, uid: int, now: float, due, served, sut) -> None:
    spec = traffic.spec(uid)
    req = sut.request(uid, traffic.prompt(uid), spec.output_len)
    eng.submit(req)
    served[uid] = Served(req, spec.prompt_len, due, now)


def backlog(eng, traffic, batch: int, depth: int, window_s: float, sut,
            on_start: Callable[[], None], on_end: Callable[[], None],
            spans: bool) -> Window:
    served: Dict[int, Served] = {}
    nxt = 0

    def top_up():
        nonlocal nxt
        while len(eng.queue) < depth:
            _submit(eng, traffic, nxt, time.perf_counter(), None, served,
                    sut)
            nxt += 1

    top_up()
    for _ in range(10 * batch):
        live = eng.step()
        top_up()
        if live == batch:
            break
    else:
        raise RuntimeError(f"the engine never filled its {batch} lanes")
    on_start()
    ws = time.perf_counter()
    ticks = []
    while True:
        t0 = time.perf_counter()
        with _span("bench.tick", spans):
            eng.step()
        t1 = time.perf_counter()
        ticks.append((t0, t1))
        with _span("bench.top_up", spans):
            top_up()
        if t1 - ws >= window_s:
            break
    we = ticks[-1][1]
    on_end()
    attempted = [u for u, s in served.items()
                 if s.req.token_times and s.req.token_times[-1] > ws
                 and s.req.token_times[0] <= we]
    return Window(ws, we, ticks, served, attempted, [])


def poisson(eng, traffic, warm_in_s: float, window_s: float, sut,
            on_start: Callable[[], None], on_end: Callable[[], None],
            spans: bool) -> Window:
    served: Dict[int, Served] = {}
    base = time.perf_counter()
    ws, we = base + warm_in_s, base + warm_in_s + window_s
    nxt, live, started = 0, 0, False
    ticks, late = [], []
    while True:
        now = time.perf_counter()
        if not started and now >= ws:
            on_start()
            started = True
        if now >= we:
            break
        with _span("bench.submit", spans):
            while base + traffic.spec(nxt).arrival_s <= now:
                due = base + traffic.spec(nxt).arrival_s
                if due >= we:
                    break
                _submit(eng, traffic, nxt, now, due, served, sut)
                if due >= ws:
                    late.append(now - due)
                nxt += 1
        if live or eng.queue:
            t0 = time.perf_counter()
            with _span("bench.tick", spans):
                live = eng.step()
            t1 = time.perf_counter()
            if t0 >= ws:
                ticks.append((t0, t1))
        else:
            nap = base + traffic.spec(nxt).arrival_s - time.perf_counter()
            if nap > 0:
                with _span("bench.wait_arrival", spans):
                    time.sleep(min(nap, 0.001))
    on_end()
    attempted = [u for u, s in served.items() if ws <= s.due < we]
    drained = True
    deadline = time.perf_counter() + DRAIN_LIMIT_S
    while any(len(served[u].req.generated) < served[u].req.max_new_tokens
              for u in attempted):
        if time.perf_counter() > deadline:
            drained = False
            break
        live = eng.step()
    return Window(ws, we, ticks, served, attempted, late, drained)

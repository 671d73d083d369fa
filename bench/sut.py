"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here: the
model configuration type (built by the configuration's architecture,
``bench/architectures/``), ``ServeEngine`` and its ``Request``, the engine's
own trace events (``trace=True``), and the HLO text of its compiled step,
whose ``op_name`` metadata carries the kernels' named scopes.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from bench import catalog


def rehearsal_model(model: Dict, serving: Dict) -> Tuple[Dict, Dict]:
    """The configuration and serving settings cut to the CPU rehearsal's
    tiny size, as the configuration's architecture cuts them."""
    return catalog.architecture(model["reference"]).rehearsal(model, serving)


def model_config(model: Dict):
    """The program's ModelConfig for a configuration file, as the
    configuration's architecture builds it."""
    return catalog.architecture(model["reference"]).model_config(model)


def build_engine(cfg, params, serving: Dict, trace: bool):
    from repro.serve.engine import ServeEngine

    return ServeEngine(
        cfg, params, batch_size=int(serving["batch"]),
        max_len=int(serving["max_len"]), da_mode=serving["da_mode"],
        runtime="paged", page_size=int(serving["page_size"]),
        prefill_chunk=int(serving["prefill_chunk"]),
        paged_attn=serving["paged_attn"], kv_dtype=serving["kv_dtype"],
        trace=trace)


def request(uid: int, prompt, max_new_tokens: int):
    from repro.serve.engine import Request

    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new_tokens)


def step_hlo(eng, shape: Tuple[int, int]) -> str:
    """Optimized HLO text of the engine's step at one (rows, T) shape.
    Lowering traces the step again; compiling finds the executable in the
    persistent cache that warmup() filled."""
    import jax.numpy as jnp

    from repro.serve.scheduler import mk_positions

    rt = eng._rt
    bw, ts = shape
    args = (rt.params, rt.caches, jnp.zeros((bw, ts), jnp.int32),
            mk_positions(rt.cfg, jnp.full((bw, ts), rt.pad_pos, jnp.int32)),
            jnp.zeros((bw, rt.W), jnp.int32), jnp.zeros((bw,), jnp.int32))
    return rt._step.lower(*args).compile().as_text()


@dataclasses.dataclass
class Call:
    """One call of the engine's step, rebuilt from its trace events."""

    kind: str                        # "prefill" | "decode"
    t0: float
    dur: float
    rows: List[Tuple[int, int]]      # (tokens, first position) per real row
    head_rows: int                   # rows whose logits became a token
    shape: Tuple[int, int]           # (rows, T) of the compiled step


def calls_from_events(events: Sequence, prompt_len: Dict[int, int],
                      window: Tuple[float, float],
                      prefill_lanes: int) -> List[Call]:
    """Per-call work of the window from the engine's trace: ``prefill``
    and ``decode`` spans on the scheduler track, ``prefill_chunk`` spans
    (tokens, pos) per row, and ``token`` instants stamped with the call's
    end. A decode row whose request then holds n tokens ran position
    prompt_len + n - 2."""
    chunks: Dict[float, List[Tuple[int, int]]] = defaultdict(list)
    tokens: Dict[float, List[Tuple[int, int]]] = defaultdict(list)
    for ev in events:
        if ev.name == "prefill_chunk":
            chunks[ev.ts].append((int(ev.args["tokens"]),
                                  int(ev.args["pos"])))
        elif ev.name == "token" and ev.track.startswith("req:"):
            tokens[ev.ts].append((int(ev.track[4:]), int(ev.args["n"])))
    stamps = sorted(tokens)

    def at(t: float):
        i = bisect.bisect_left(stamps, t - 1e-7)
        out = []
        while i < len(stamps) and stamps[i] <= t + 1e-7:
            out.extend(tokens[stamps[i]])
            i += 1
        return out

    calls: List[Call] = []
    for ev in events:
        if ev.track != "scheduler" or not window[0] <= ev.ts < window[1]:
            continue
        end = ev.ts + ev.dur
        if ev.name == "prefill":
            rows = chunks.get(ev.ts, [])
            calls.append(Call("prefill", ev.ts, ev.dur, rows, len(at(end)),
                              (prefill_lanes, int(ev.args["t_step"]))))
        elif ev.name == "decode":
            toks = at(end)
            rows = [(1, prompt_len[uid] + n - 2) for uid, n in toks]
            calls.append(Call("decode", ev.ts, ev.dur, rows, len(rows),
                              (int(ev.args["width"]), 1)))
    return calls

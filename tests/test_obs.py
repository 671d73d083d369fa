"""Observability layer: registry/instrument semantics, trace ring + span
balance, Chrome-trace / Prometheus export validity, the two serving
acceptance properties — tokens bit-identical with tracing on/off, and trace
spans reconstructing TTFT/ITL exactly from the shared perf_counter clock —
and the scheduler's phase spans, recorded on both the recorder's and the
profiler's clock."""
import dataclasses
import glob
import json
import re
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduce_for_smoke
from repro.models.model import init_model
from repro.obs import (
    METRICS_SCHEMA_VERSION,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Observability,
    TraceRecorder,
    chrome_trace,
    prometheus_text,
    validate_chrome_trace,
    validate_prometheus_text,
)
from repro.obs import SCHED_TRACK
from repro.obs import check as obs_check
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.key(0)
MAX_NEW = 4


# ---------------------------------------------------------------------------
# instruments / registry (pure)
# ---------------------------------------------------------------------------
def test_counter_labels_and_total():
    reg = MetricsRegistry()
    c = reg.counter("toks", "tokens emitted")
    c.inc()
    c.inc(3, backend="fused")
    c.inc(2, backend="gather")
    assert c.value() == 1
    assert c.value(backend="fused") == 3
    assert c.total == 6
    # get-or-create returns the same instrument; kind conflicts are errors
    assert reg.counter("toks") is c
    with pytest.raises(ValueError):
        reg.gauge("toks")


def test_gauge_last_write_wins():
    g = MetricsRegistry().gauge("lanes")
    g.set(3)
    g.set(1)
    assert g.value() == 1.0


def test_histogram_streaming_percentiles():
    h = MetricsRegistry().histogram("lat", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in [0.0005] * 50 + [0.05] * 50:
        h.observe(v)
    assert h.count() == 100
    assert h.sum() == pytest.approx(50 * 0.0005 + 50 * 0.05)
    # p25 lands in the first bucket, p75 in the 0.1 bucket — the estimate
    # must stay inside the bucket that holds the true quantile
    assert h.percentile(25) <= 0.001
    assert 0.01 <= h.percentile(75) <= 0.1
    # out-of-range observations land in the +Inf bin, not a crash
    h.observe(50.0)
    assert h.count() == 101
    assert h.percentile(100) > 1.0


def test_snapshot_schema_and_determinism():
    reg = MetricsRegistry()
    reg.counter("b").inc(2)
    reg.counter("a").inc(1, mode="x")
    reg.histogram("h").observe(0.01)
    snap = reg.snapshot()
    assert snap["metrics_schema_version"] == METRICS_SCHEMA_VERSION
    assert snap["b"] == 2 and snap["a{mode=x}"] == 1
    assert snap["h"]["count"] == 1
    assert list(snap) == list(reg.snapshot())  # deterministic order


def test_disabled_registry_short_circuits():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.inc(5)
    g.set(2)
    h.observe(0.1)
    assert c.total == 0 and g.value() == 0 and h.count() == 0
    assert isinstance(c, Counter) and isinstance(g, Gauge) \
        and isinstance(h, Histogram)
    # snapshot carries only the schema stamp — no phantom series
    assert reg.snapshot() == {
        "metrics_schema_version": METRICS_SCHEMA_VERSION}


# ---------------------------------------------------------------------------
# trace recorder (pure)
# ---------------------------------------------------------------------------
def test_span_balance_survives_ring_wraparound():
    tr = TraceRecorder(capacity=8)
    for i in range(20):  # 40 events through an 8-slot ring
        with tr.span("work", f"req:{i % 3}"):
            pass
    assert len(tr) == 8
    assert tr.dropped == 32
    # balance is judged on lifetime depth counters, not surviving events —
    # evicted "B" events cannot fake an open span
    assert tr.span_balance() == {}
    tr.begin("open", "req:9")
    assert tr.span_balance() == {"req:9": 1}


def test_span_closes_on_exception():
    tr = TraceRecorder()
    with pytest.raises(RuntimeError):
        with tr.span("work", "t"):
            raise RuntimeError("body failed")
    assert tr.span_balance() == {}


def test_disabled_tracer_records_nothing():
    tr = TraceRecorder(enabled=False)
    tr.begin("a", "t")
    tr.instant("b", "t")
    tr.end("a", "t")
    assert len(tr) == 0 and tr.span_balance() == {}


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the name and
    the perf_counter interval of every annotation entered."""

    def __init__(self):
        self.spans = []
        rec = self

        class Annotation:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                rec.spans.append((self.name, self.t0, time.perf_counter()))
                return False

        self.cls = Annotation


@pytest.fixture
def annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    return ann


def test_phase_records_one_span_on_both_clocks(annotations):
    tr = TraceRecorder()
    with tr.phase("sched.pack", phase="decode") as args:
        args.update(rows=3)  # an arg learned inside the span
    (ev,) = tr.events
    assert (ev.name, ev.ph, ev.track) == ("sched.pack", "X", SCHED_TRACK)
    assert ev.args == {"phase": "decode", "rows": 3}
    ((name, a0, a1),) = annotations.spans
    assert name == "sched.pack"
    assert a0 <= ev.ts and ev.ts + ev.dur <= a1  # the same interval
    with pytest.raises(RuntimeError):
        with tr.phase("sched.sample"):
            raise RuntimeError("body failed")
    assert [e.name for e in tr.events][-1] == "sched.sample"
    assert [a[0] for a in annotations.spans][-1] == "sched.sample"


def test_disabled_phase_is_one_shared_null_context(annotations):
    tr = TraceRecorder(enabled=False)
    ctx = tr.phase("sched.tick")
    assert tr.phase("sched.d2h", bytes=4) is ctx  # nothing built per call
    with ctx as args:
        args.update(rows=2)
    assert len(tr) == 0 and annotations.spans == []


# ---------------------------------------------------------------------------
# exporters + validators
# ---------------------------------------------------------------------------
def _sample_recorder():
    tr = TraceRecorder()
    tr.instant("submit", "req:0", ts=1.0)
    tr.begin("running", "req:0", ts=1.5)
    tr.complete("tick", "scheduler", 1.4, 0.3, lanes=1)
    tr.instant("token", "req:0", ts=2.0, n=1)
    tr.end("running", "req:0", ts=2.5)
    return tr


def test_chrome_trace_export_is_valid_and_complete():
    tr = _sample_recorder()
    obj = chrome_trace(tr)
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    names = {(e["ph"], e["name"]) for e in evs}
    assert ("i", "submit") in names and ("X", "tick") in names
    # track metadata names every track so Perfetto labels the rows
    meta = {e["args"]["name"] for e in evs if e["ph"] == "M"
            and e["name"] == "thread_name"}
    assert {"req:0", "scheduler"} <= meta
    # timestamps exported in microseconds on the shared clock
    submit = next(e for e in evs if e["name"] == "submit")
    assert submit["ts"] == pytest.approx(1.0e6)
    assert obj["otherData"]["metrics_schema_version"] == \
        METRICS_SCHEMA_VERSION


def test_chrome_trace_validator_catches_imbalance():
    tr = TraceRecorder()
    tr.begin("running", "req:0")  # B without E
    errs = validate_chrome_trace(chrome_trace(tr))
    assert errs and any("balance" in e or "unclosed" in e for e in errs)


def test_prometheus_export_is_valid():
    reg = MetricsRegistry()
    reg.counter("sched_out_tokens", "tokens").inc(12)
    reg.gauge("kv_used_pages").set(3)
    h = reg.histogram("req_ttft_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = prometheus_text(reg)
    assert validate_prometheus_text(text) == []
    lines = text.splitlines()
    assert "# TYPE sched_out_tokens counter" in lines
    assert "sched_out_tokens 12" in lines
    # histogram exports cumulative buckets plus the +Inf/sum/count triple
    assert 'req_ttft_seconds_bucket{le="0.1"} 1' in lines
    assert 'req_ttft_seconds_bucket{le="+Inf"} 2' in lines
    assert "req_ttft_seconds_count 2" in lines


def test_prometheus_validator_catches_garbage():
    assert validate_prometheus_text("not a metric line at all!") != []
    # histogram missing its _count is incomplete
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="+Inf"} 1\n'
           "h_sum 0.5\n")
    assert validate_prometheus_text(bad) != []


def test_check_cli_accepts_valid_rejects_invalid(tmp_path, capsys):
    good_trace = tmp_path / "trace.json"
    good_trace.write_text(json.dumps(chrome_trace(_sample_recorder())))
    reg = MetricsRegistry()
    reg.counter("c").inc()
    good_prom = tmp_path / "metrics.prom"
    good_prom.write_text(prometheus_text(reg))
    assert obs_check.main([str(good_trace), str(good_prom)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "B"}]}))
    assert obs_check.main([str(bad)]) == 1
    assert obs_check.main([]) == 2


# ---------------------------------------------------------------------------
# serving acceptance: identity, balance, exact latency reconstruction
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduce_for_smoke(ARCHS["qwen3-8b"]),
                              moe_dropless=True)
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(7)
    prompts = {uid: rng.integers(0, cfg.vocab, 3 + uid) for uid in range(4)}
    return cfg, params, prompts


def _serve(cfg, params, prompts, **kw):
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=8,
                      **kw)
    for uid, pr in prompts.items():
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
    done = eng.run()
    return eng, {u: r.generated for u, r in done.items()}


def _comparable_registry_view(reg):
    """Counter totals + histogram observation counts — everything in the
    registry that must be invariant to wall-clock (sums/percentiles of
    timing histograms legitimately differ between runs)."""
    out = {}
    for name, inst in reg.instruments().items():
        if isinstance(inst, Counter):
            out[name] = inst.total
        elif isinstance(inst, Histogram):
            out[name] = inst.count()
    return out


def test_tokens_and_counters_identical_tracing_on_off(setup):
    """Acceptance: tracing must never perturb decode — greedy tokens are
    bit-identical with tracing on vs off, and every counter/observation
    count in the registry agrees."""
    cfg, params, prompts = setup
    eng_off, out_off = _serve(cfg, params, prompts, trace=False)
    eng_on, out_on = _serve(cfg, params, prompts, trace=True)
    assert out_on == out_off
    assert _comparable_registry_view(eng_on.obs.registry) == \
        _comparable_registry_view(eng_off.obs.registry)
    assert len(eng_off.obs.tracer) == 0  # off really is off
    assert len(eng_on.obs.tracer) > 0
    # the read-back is split into wait + copy on both sides alike
    for eng in (eng_on, eng_off):
        assert eng.obs.registry.get("sched_d2h_bytes").total > 0
        assert eng.obs.registry.get("sched_device_calls").total > 0


def test_span_balance_through_preempt_defrag_spec_stress(setup):
    """Every span opened is closed across the full lifecycle gauntlet:
    admit → forced preempt → re-admit → defrag → speculative rounds (with
    rollback) → finish.  The exported trace validates as Chrome JSON."""
    from repro.core.da import DAConfig
    from repro.core.freeze import freeze_model
    from repro.spec import SpecConfig

    cfg, params, prompts = setup
    art = freeze_model(params, DAConfig(x_signed=True),
                       mode="bitplane_stacked", model_cfg=cfg)
    spec = SpecConfig(provider="bitplane", gamma=2, draft_x_bits=6,
                      disable_below=0.0)
    eng = ServeEngine(cfg, art.params, batch_size=2, max_len=32, page_size=4,
                      spec=spec, trace=True)
    for uid, pr in prompts.items():
        # long enough that one speculative tick cannot finish a request —
        # the preemption below needs a live lane to evict
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=12))
    eng.step()
    sched = eng._rt
    victims = [i for i, l in enumerate(sched.lanes) if l is not None]
    assert victims, "tick finished every request; nothing left to preempt"
    sched._preempt(victims[-1])
    sched.defrag()
    done = eng.run()
    assert sorted(done) == sorted(prompts)
    m = eng.metrics()
    assert m["preemptions"] >= 1
    assert m["spec"]["rounds"] > 0
    assert m["pool"]["used_pages"] == 0
    assert eng.obs.tracer.span_balance() == {}
    assert validate_chrome_trace(chrome_trace(eng.obs.tracer)) == []
    ticks, phases = _phase_spans(eng.obs.tracer)
    names = {re.sub(r"\[.*", "", e.name) for e in phases}
    assert {"spec_draft", "spec_verify", "sched.sample"} <= names
    assert any((e.args or {}).get("phase") == "spec" for e in phases
               if e.name == "sched.sample")
    snap = eng.metrics_snapshot()
    assert snap["sched_d2h_bytes{phase=spec_verify}"] > 0
    assert snap["sched_preemptions"] >= 1
    assert snap["spec_rounds"] > 0
    assert validate_prometheus_text(prometheus_text(eng.obs.registry)) == []


PHASES = ("sched.admit", "sched.pack", "sched.wait", "sched.d2h",
          "sched.sample", "sched.retire")
DISPATCH = re.compile(r"^(paged_step|spec_draft|spec_ingest|spec_verify)"
                      r"\[(\d+)x(\d+)\]$")


def _phase_spans(tracer):
    """The ``sched.tick`` spans and the phase spans inside them, checked:
    every phase lies inside exactly one tick, and no two phases overlap."""
    xs = [e for e in tracer.events if e.ph == "X" and e.track == SCHED_TRACK]
    ticks = [e for e in xs if e.name == "sched.tick"]
    phases = sorted((e for e in xs
                     if e.name in PHASES or DISPATCH.match(e.name)),
                    key=lambda e: e.ts)
    for e in phases:
        inside = [t for t in ticks
                  if t.ts <= e.ts and e.ts + e.dur <= t.ts + t.dur]
        assert len(inside) == 1, f"{e.name} at {e.ts}: {len(inside)} ticks"
    for a, b in zip(phases, phases[1:]):
        assert a.ts + a.dur <= b.ts, f"{a.name} overlaps {b.name}"
    return ticks, phases


def test_sched_phase_spans_tile_each_tick(setup):
    """One ``sched.tick`` per tick; admit, pack, dispatch, wait, d2h, sample
    and retire follow each other inside it without overlap; the d2h counter
    counts the rows int32 token ids of every call."""
    cfg, params, prompts = setup
    eng, _ = _serve(cfg, params, prompts, trace=True)
    tr = eng.obs.tracer
    ticks, phases = _phase_spans(tr)
    assert len(ticks) == sum(e.name == "tick" for e in tr.events) \
        == eng.metrics()["steps"]
    assert tr.span_balance() == {}
    for t in ticks:
        mine = [e.name for e in phases
                if t.ts <= e.ts < t.ts + t.dur]
        assert mine[0] == "sched.admit" and mine[-1] == "sched.retire"
        assert mine.count("sched.admit") == mine.count("sched.retire") == 1
    reg = eng.obs.registry
    calls, d2h, pack = {}, {}, None
    for k, e in enumerate(phases):
        if e.name == "sched.pack":
            pack = e.args["phase"]
        m = DISPATCH.match(e.name)
        if m:
            # dispatch → wait → copy, each call
            assert [x.name for x in phases[k + 1 : k + 3]] == \
                ["sched.wait", "sched.d2h"]
            nbytes = int(m.group(2)) * 4  # one int32 token id a row
            assert phases[k + 2].args["bytes"] == nbytes
            calls[pack] = calls.get(pack, 0) + 1
            d2h[pack] = d2h.get(pack, 0) + nbytes
    assert set(calls) == {"prefill", "decode"}
    for ph in calls:
        assert reg.get("sched_device_calls").value(phase=ph) == calls[ph]
        assert reg.get("sched_d2h_bytes").value(phase=ph) == d2h[ph]


def test_queue_wait_is_admission_minus_submit(setup):
    cfg, params, prompts = setup
    eng, _ = _serve(cfg, params, prompts, trace=True)
    submit, waits = {}, []
    for ev in eng.obs.tracer.events:
        if ev.name == "submit":
            submit[ev.track] = ev.ts
        elif ev.name == "running" and ev.ph == "B":
            assert ev.args["queue_wait_s"] == ev.ts - submit[ev.track]
            waits.append(ev.args["queue_wait_s"])
    assert len(waits) == len(prompts) and max(waits) > 0
    h = eng.obs.registry.get("req_queue_wait_seconds")
    assert h.count() == len(waits)
    assert h.sum() == pytest.approx(sum(waits))


def test_tracing_off_enters_no_annotation(setup, annotations):
    """Off, the scheduler records no event and enters no TraceAnnotation;
    on, every annotation it enters has its recorder twin."""
    cfg, params, prompts = setup
    eng, _ = _serve(cfg, params, prompts, trace=False)
    assert len(eng.obs.tracer) == 0 and annotations.spans == []
    eng, _ = _serve(cfg, params, prompts, trace=True)
    ticks, phases = _phase_spans(eng.obs.tracer)
    assert sorted(a[0] for a in annotations.spans) == \
        sorted(e.name for e in ticks + phases)


def test_phase_spans_reach_a_profiler_capture(setup, tmp_path):
    """The annotations land on the profiler's host plane with the nesting
    the benchmark's idle-gap labels rely on: each dispatch inside a tick."""
    from jax.profiler import ProfileData

    cfg, params, prompts = setup
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=8,
                      trace=True)
    eng.warmup()
    for uid, pr in prompts.items():
        eng.submit(Request(uid=uid, prompt=pr, max_new_tokens=MAX_NEW))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith(("sched.", "paged_step["))]
    ticks = [s for s in spans if s[0] == "sched.tick"]
    assert len(ticks) == eng.metrics()["steps"]
    steps = [s for s in spans if s[0].startswith("paged_step[")]
    assert steps and all(
        any(t[1] <= s[1] and s[2] <= t[2] for t in ticks) for s in steps)
    assert {s[0] for s in spans} >= set(PHASES)


def test_trace_reconstructs_ttft_itl_exactly(setup):
    """The token instants carry the SAME perf_counter stamps the scheduler
    wrote into Request.token_times — so TTFT/ITL percentiles recomputed
    from the trace equal latency_metrics() to float precision, not merely
    within sampling noise."""
    cfg, params, prompts = setup
    eng, _ = _serve(cfg, params, prompts, trace=True)
    m = eng.metrics()
    events = list(eng.obs.tracer.events)
    submit_ts, token_ts = {}, {}
    for ev in events:
        if ev.ph == "i" and ev.track.startswith("req:"):
            uid = int(ev.track.split(":")[1])
            if ev.name == "submit":
                submit_ts[uid] = ev.ts
            elif ev.name == "token":
                token_ts.setdefault(uid, []).append(ev.ts)
    assert sorted(token_ts) == sorted(prompts)
    ttft = [token_ts[u][0] - submit_ts[u] for u in sorted(token_ts)]
    itl = [b - a for u in token_ts
           for a, b in zip(token_ts[u], token_ts[u][1:])]
    assert float(np.percentile(ttft, 50)) * 1e3 == \
        pytest.approx(m["ttft_p50_ms"], abs=1e-9)
    assert float(np.percentile(itl, 50)) * 1e3 == \
        pytest.approx(m["itl_p50_ms"], abs=1e-9)
    assert all(len(ts) == MAX_NEW for ts in token_ts.values())


def test_slot_runtime_traces_lifecycle(setup):
    """The legacy slot runtime rides the same Observability bundle: spans
    balance, the trace validates, and the shared metrics() core agrees."""
    cfg, params, prompts = setup
    eng, out = _serve(cfg, params, prompts, runtime="slots", trace=True)
    assert sorted(out) == sorted(prompts)
    assert eng.obs.tracer.span_balance() == {}
    assert validate_chrome_trace(chrome_trace(eng.obs.tracer)) == []
    m = eng.metrics()
    assert m["runtime"] == "slots"
    assert m["out_tokens"] == len(prompts) * MAX_NEW
    assert eng.metrics_snapshot()["sched_out_tokens"] == \
        len(prompts) * MAX_NEW


def test_observability_bundle_defaults():
    obs = Observability.make()
    assert obs.registry.enabled and not obs.tracer.enabled
    obs_t = Observability.make(trace=True)
    assert obs_t.tracer.enabled
    obs_off = Observability.make(metrics=False)
    assert not obs_off.registry.enabled

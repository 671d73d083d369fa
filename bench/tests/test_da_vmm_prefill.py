"""The DA roofline of the prefill calls alone, on the recorded TPU probe
(bench/tests/record_probe.py): each step module takes the kind of the
engine call that dispatched it, found by time across the two clocks, and
the prefill and decode shares of ``da_*`` device time add up to what
``da_vmm_roofline`` divides by."""
import importlib.util
import pathlib

import pytest

from bench import catalog, readings, tracing
from bench.peaks import peaks_for
from bench.sut import Call

DATA = pathlib.Path(__file__).parent / "data"
_spec = importlib.util.spec_from_file_location(
    "da_vmm_roofline_prefill",
    catalog.BENCH / "layer_metrics" / "da_vmm_roofline.prefill.py")
READER = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(READER)
PREFILL = catalog.reader("da_vmm_roofline.prefill")
#: the engine's clock runs this far behind the profiler's in the test
ENGINE_LAG_S = 1234.5


def _probe_capture(shapes):
    """The probe's three step runs, each dispatched under a
    ``paged_step[RxT]`` span of the given shape, named as the engine's
    step executable is."""
    ops, modules, _ = tracing.read_xplane(str(DATA / "probe.xplane.pb"))
    names = tracing.hlo_op_names((DATA / "probe_hlo.txt").read_text())
    modules = [("jit_counted" + m[0][len("jit_step"):], m[1], m[2])
               for m in modules]
    spans = [(f"paged_step[{r}x{t}]", m[1] - 1e-4, m[1] - 5e-5)
             for (r, t), m in zip(shapes, modules)]
    window = (modules[0][1] - 1.0, modules[-1][2] + 1.0)
    return tracing.build(ops, modules, spans, window,
                         {s: names for s in set(shapes)})


def _view(cap, kinds, shapes):
    """Engine calls on the engine's own clock: each starts (packing) a
    little before its dispatch span and lasts until its read-back."""
    calls = []
    for (_, s, _), kind, shape in zip(
            sorted(cap.spans, key=lambda sp: sp[1]), kinds, shapes):
        rows = [(shape[1], 100)] * shape[0]
        calls.append(Call(kind, s - ENGINE_LAG_S - 3e-4, 2e-3, rows,
                          shape[0], shape))
    calls.sort(key=lambda c: c.t0)
    model = catalog.config("qwen3-8b")
    return readings.RunView(model, model["serving"],
                            peaks_for("TPU v5 lite"), cap.window, [],
                            calls, cap)


def test_prefill_and_decode_shares_add_up_to_the_da_time():
    # [4x1] twice: the one-token prefill bucket and a decode of width 4
    shapes = [(4, 16), (4, 1), (4, 1)]
    kinds = ["prefill", "decode", "prefill"]
    cap = _probe_capture(shapes)
    view = _view(cap, kinds, shapes)
    by_module = READER.module_kinds(view)
    order = sorted(by_module, key=lambda i: cap.modules[i][1])
    assert [by_module[i] for i in order] == kinds
    split = READER.da_seconds_by_kind(view)
    whole = view.device_seconds(tracing.is_da)
    assert whole > 0 and split[None] == 0
    assert split["prefill"] + split["decode"] == pytest.approx(whole,
                                                               rel=1e-12)
    per_module = {i: tracing.scoped_seconds(
        tracing.Capture([o for o in cap.ops if o.module == i], cap.modules,
                        cap.spans, cap.window), tracing.is_da)
        for i in order}
    assert split["prefill"] == pytest.approx(per_module[order[0]]
                                             + per_module[order[2]])
    got = PREFILL(view)
    prefill_calls = [c for c in view.calls if c.kind == "prefill"]
    view.calls = prefill_calls
    assert got == pytest.approx(100 * view.da_least_seconds()
                                / split["prefill"])


def test_kind_follows_the_call_not_the_shape():
    shapes = [(4, 1), (4, 1), (4, 1)]
    for kinds in (["decode"] * 3, ["prefill", "decode", "decode"]):
        cap = _probe_capture(shapes)
        view = _view(cap, kinds, shapes)
        split = READER.da_seconds_by_kind(view)
        whole = view.device_seconds(tracing.is_da)
        if "prefill" in kinds:
            assert 0 < split["prefill"] < whole
            assert PREFILL(view) > 0
        else:
            assert split["prefill"] == 0 and split["decode"] == \
                pytest.approx(whole)
            assert PREFILL(view) is None


def test_nothing_to_read_returns_none():
    model = catalog.config("qwen3-8b")
    view = readings.RunView(model, model["serving"],
                            peaks_for("TPU v5 lite"), (0.0, 1.0), [],
                            None, None)
    assert PREFILL(view) is None
    view.calls = []
    assert PREFILL(view) is None

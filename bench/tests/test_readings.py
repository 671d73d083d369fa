"""Per-layer readers over a synthetic window: each share is logical work
at the published peak over measured time, and readers with nothing to read
return None."""
import pytest

from bench import catalog, readings, tracing
from bench.peaks import peaks_for
from bench.sut import Call
from bench.tracing import Capture, Op


def _view(calls, ops):
    model = catalog.config("qwen3-8b")
    cap = Capture(ops, [], [], (0.0, 1.0)) if ops is not None else None
    return readings.RunView(model, model["serving"],
                            peaks_for("TPU v5 lite"), (0.0, 1.0),
                            [(0.0, 0.02), (0.5, 0.53)], calls, cap)


def test_da_roofline_is_least_time_over_da_device_time():
    calls = [Call("decode", 0.1, 0.01, [(1, 100)] * 32, 32, (32, 1))]
    view = _view(calls, [Op("fusion.1", 0.1, 0.008,
                            op_name="x/da_bitplane_stacked/dot")])
    m = view.model
    d, ff, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    qkv = d * (32 * 128 + 2 * 8 * 128)
    codes = 8 * (qkv + 4096 * d + 3 * d * ff) + d * v
    # decode at M=32 is bandwidth-bound: ~codes / 819 GB/s
    least = view.da_least_seconds()
    # plus scales and bf16 activations in and out: a few percent more
    assert codes / 819e9 < least < 1.05 * codes / 819e9
    got = catalog.reader("da_vmm_roofline")(view)
    assert got == pytest.approx(100 * least / 0.008)


def test_attention_roofline_and_idle_and_mfu():
    calls = [Call("decode", 0.1, 0.01, [(1, 511)] * 32, 32, (32, 1))]
    ops = [Op("paged_attn_fused_fp.3", 0.2, 0.004,
              op_name="x/paged_attn_fused_fp/pallas_call"),
           Op("fusion.9", 0.3, 0.1, op_name="x/norm")]
    view = _view(calls, ops)
    pages = 2 * 32 * 32 * 16 * 8 * 128 * 2 * 8   # K+V, 32 rows, 8 layers
    assert view.attn_least_seconds() == pytest.approx(pages / 819e9,
                                                      rel=0.01)
    assert catalog.reader("paged_attn_roofline")(view) == pytest.approx(
        100 * view.attn_least_seconds() / 0.004)
    assert catalog.reader("device_idle")(view) == pytest.approx(
        100 * (1 - 0.104))
    mfu = catalog.reader("step_mfu")(view)
    assert 0 < mfu < 1
    assert catalog.reader("tick_ms.backlog")(view) == pytest.approx(25.0)


def test_readers_with_nothing_to_read_return_none():
    view = _view(None, None)
    for name in ("device_idle", "step_mfu", "da_vmm_roofline",
                 "paged_attn_roofline"):
        assert catalog.reader(name)(view) is None
    empty = _view([], [])
    assert catalog.reader("da_vmm_roofline")(empty) is None


def test_percentile_is_linear_between_order_statistics():
    assert readings.percentile([1, 2, 3, 4, 5], 50) == 3
    assert readings.percentile(list(range(101)), 95) == 95
    assert readings.percentile([0.0, 10.0], 95) == pytest.approx(9.5)


def test_tracing_helpers_match():
    assert tracing.step_shape("paged_step[4x16]") == (4, 16)
    assert tracing.step_shape("bench.tick") is None

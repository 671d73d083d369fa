"""The trace reduction: busy union, idle gaps, scope attribution, clocks."""
import pathlib

import pytest

from bench import tracing
from bench.tracing import Op

DATA = pathlib.Path(__file__).parent / "data"


def test_union_merges_overlaps_and_drops_empty():
    got = tracing.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (5, 6), (5.5, 7)])
    assert got == [(0, 2), (3, 4), (5, 7)]


def test_busy_and_idle_inside_the_window():
    ops = [Op("a", 0.0, 1.0), Op("b", 0.5, 1.0), Op("c", 3.0, 1.0),
           Op("d", 9.0, 5.0)]
    window = (1.0, 10.0)
    assert tracing.busy_seconds(ops, window) == pytest.approx(0.5 + 1 + 1)
    assert tracing.idle_gaps(ops, window) == [(1.5, 3.0), (4.0, 9.0)]


def test_gap_label_is_innermost_open_span():
    spans = [("bench.tick", 0.0, 10.0), ("paged_step[32x1]", 2.0, 3.0)]
    assert tracing.label_gap((2.2, 2.8), spans) == "paged_step[32x1]"
    assert tracing.label_gap((5.0, 6.0), spans) == "bench.tick"
    assert tracing.label_gap((11.0, 12.0), spans) == "no host span"


def test_hlo_metadata_gives_each_instruction_its_scope():
    text = "\n".join([
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(counted)/while/body/closed_call/'
        'jit(_da_matmul_jit)/da_bitplane_stacked/dot_general" '
        'stack_frame_id=3}',
        '  ROOT %paged_attn_fused_fp.1 = bf16[8]{0} custom-call(%x), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(counted)/paged_attn_fused_fp/pallas_call"}',
        '  %copy.2 = f32[8]{0} copy(%y)',
    ])
    names = tracing.hlo_op_names(text)
    assert names["fusion.5"].endswith("da_bitplane_stacked/dot_general")
    assert "copy.2" not in names
    assert tracing.is_da(Op("fusion.5", 0, 1, op_name=names["fusion.5"]))
    assert not tracing.is_da(Op("x", 0, 1, op_name="jit(f)/dag_x/add"))
    assert tracing.is_paged_attn(
        Op("paged_attn_fused_fp.1", 0, 1,
           op_name=names["paged_attn_fused_fp.1"]))


def test_instruction_name_from_event_text():
    ev = ("%fusion.51 = f32[8]{0:T(128)S(1)} fusion(bf16[8,1024]{1,0} "
          "%get-tuple-element.82), kind=kLoop")
    assert tracing.instr_name(ev) == "fusion.51"
    assert tracing.instr_name("%while.2 = (s32[], bf16[8]) while(%t)") == \
        "while.2"


def test_build_aligns_clocks_and_attributes_by_module_shape():
    # two step runs of different shapes; the device clock runs 5 ms ahead
    spans = [("bench.tick", 0.0, 0.050), ("paged_step[32x1]", 0.001, 0.002),
             ("paged_step[4x16]", 0.020, 0.021)]
    modules = [("jit_counted(1)", 0.006, 0.016),
               ("jit_counted(2)", 0.025, 0.035)]
    ops = [Op("fusion.1", 0.007, 0.004), Op("fusion.1", 0.026, 0.004),
           Op("fusion.2", 0.031, 0.002)]
    hlo = {(32, 1): {"fusion.1": "x/da_bitplane_stacked/dot"},
           (4, 16): {"fusion.1": "x/norm/add",
                     "fusion.2": "x/paged_attn_fused_fp/pallas_call"}}
    cap = tracing.build(ops, modules, spans, (0.0, 0.050), hlo)
    assert [round(o.start, 6) for o in cap.ops] == [0.002, 0.021, 0.026]
    assert tracing.scoped_seconds(cap, tracing.is_da) == pytest.approx(0.004)
    assert tracing.scoped_seconds(cap, tracing.is_paged_attn) == \
        pytest.approx(0.002)
    bd = tracing.breakdown(cap)
    assert bd["device_ops"][0] == ["da_bitplane_stacked/dot",
                                   pytest.approx(0.004)]
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(0.040)


def test_recorded_tpu_capture():
    """A capture recorded on one TPU v5e (bench/tests/record_probe.py): a
    jitted step with a scanned DA matmul and a fused paged-attention read,
    run three times."""
    ops, modules, spans = tracing.read_xplane(str(DATA / "probe.xplane.pb"))
    assert len(modules) == 3 and all(m[0].startswith("jit_step")
                                     for m in modules)
    assert len(ops) == 198
    names = tracing.hlo_op_names((DATA / "probe_hlo.txt").read_text())
    steps = [s for s in spans if s[0].startswith("hostspan")]
    assert steps == []  # the probe's own spans are not benchmark spans
    fake_spans = [(f"paged_step[8x1]", m[1] - 1e-4, m[1]) for m in modules]
    cap = tracing.build(ops, modules, fake_spans,
                        (modules[0][1] - 1.0, modules[-1][2] + 1.0),
                        {(8, 1): names}, prefix="jit_step")
    da = tracing.scoped_seconds(cap, tracing.is_da)
    attn = tracing.scoped_seconds(cap, tracing.is_paged_attn)
    busy = tracing.busy_seconds(cap.ops, cap.window)
    assert 0 < da < busy and 0 < attn < busy
    per_module = [m[2] - m[1] for m in cap.modules]
    assert busy <= sum(per_module) + 1e-9

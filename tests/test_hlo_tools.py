"""Parser hardening tests for the HLO text scanner (repro.analysis.hlo,
re-exported through the legacy repro.launch.hlo_tools surface).

The original single-regex parser missed multi-line op definitions, nested
tuple result types, and layout tiles with parenthesized suffixes — each is
pinned here against hand-built HLO snippets plus a real jit lowering.
"""
import jax
import jax.numpy as jnp

from repro.analysis.hlo import (
    HloOp,
    bytes_by_op_kind,
    custom_call_target,
    iter_ops,
    op_kinds,
    ops_of_kind,
    shape_bytes,
    shape_dtypes,
    top_collectives,
)

# -- shape/byte accounting ---------------------------------------------------


def test_shape_bytes_scalar_and_tuple():
    assert shape_bytes("f32[2,64]") == 2 * 64 * 4
    assert shape_bytes("s32[]") == 4
    assert shape_bytes("(f32[2,64]{1,0}, (s32[], u8[]))") == 512 + 4 + 1
    assert shape_bytes("token[]") == 0  # unknown dtype contributes nothing


def test_shape_dtypes():
    assert shape_dtypes("(f32[2]{0}, s8[4,4])") == {"f32", "s8"}


# -- logical-line joining ----------------------------------------------------


def test_multiline_op_definition_is_joined():
    txt = (
        "  %long.name.1 = f32[8,128]{1,0}\n"
        "      dot(%a, %b),\n"
        '      metadata={op_name="jit(f)/dot_general"}\n'
    )
    ops = list(iter_ops(txt))
    assert len(ops) == 1
    assert ops[0].kind == "dot"
    assert ops[0].result_bytes == 8 * 128 * 4


def test_wrapped_attribute_line_does_not_start_new_op():
    """A wrapped ``metadata={...}`` continuation has ``key=`` syntax that a
    naive line-anchored regex mistakes for a new op head."""
    txt = (
        "  %x = f32[4]{0} add(%a, %b),\n"
        "      metadata={op_name=\"while(body)/add\" source_file=\"f.py\"}\n"
        "  %y = f32[4]{0} multiply(%x, %b)\n"
    )
    kinds = [op.kind for op in iter_ops(txt)]
    assert kinds == ["add", "multiply"]


def test_nested_tuple_result_type():
    txt = "  %t = (f32[2,64]{1,0}, (s32[], u8[])) tuple(%a, %b, %c)\n"
    ops = list(iter_ops(txt))
    assert len(ops) == 1
    assert ops[0].kind == "tuple"
    assert ops[0].result_bytes == 2 * 64 * 4 + 4 + 1


def test_layout_tile_with_parenthesized_suffix():
    txt = "  %p = f32[8,128]{1,0:T(8,128)} parameter(0)\n"
    ops = list(iter_ops(txt))
    assert len(ops) == 1
    assert ops[0].kind == "parameter"
    assert ops[0].result_bytes == 8 * 128 * 4


def test_region_opener_brace_on_op_line():
    txt = (
        "fused_computation {\n"
        "  %p0 = s8[16]{0} parameter(0)\n"
        "  { %r = s8[16]{0} negate(%p0)\n"
        "}\n"
    )
    kinds = [op.kind for op in iter_ops(txt)]
    assert kinds == ["parameter", "negate"]


def test_custom_call_target_extraction():
    txt = ('  %cc = f32[4]{0} custom-call(%a), '
           'custom_call_target="tpu_custom_call", api_version=1\n')
    (op,) = iter_ops(txt)
    assert op.kind == "custom-call"
    assert custom_call_target(op) == "tpu_custom_call"


def test_collectives_count_start_not_done():
    txt = (
        "  %ag = f32[8]{0} all-gather-start(%a)\n"
        "  %agd = f32[8]{0} all-gather-done(%ag)\n"
        "  %ar = f32[8]{0} all-reduce(%b)\n"
    )
    names = [name for name, _, _ in top_collectives(txt)]
    assert sorted(names) == ["ag", "ar"]


# -- real lowering round-trip ------------------------------------------------


def test_real_jit_lowering_roundtrip():
    def f(x, w):
        return x @ w

    x = jnp.zeros((4, 16), jnp.float32)
    w = jnp.zeros((16, 8), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    kinds = op_kinds(txt)
    assert sum(kinds.values()) > 0
    dots = ops_of_kind(txt, "dot")
    fusions = ops_of_kind(txt, "fusion")
    assert dots or fusions  # the matmul is a dot, possibly fused
    if dots:
        assert dots[0][1] == 4 * 8 * 4  # [4, 8] f32 result, exact bytes
    agg = dict((k, b) for k, b, _ in bytes_by_op_kind(txt))
    assert "parameter" not in agg  # bookkeeping kinds are excluded


def test_result_bytes_property():
    op = HloOp(name="x", kind="add", type_str="bf16[2,3]", line_no=1,
               text="")
    assert op.result_bytes == 2 * 3 * 2


# -- the legacy shim ---------------------------------------------------------


def test_launch_hlo_tools_reexports_are_identical():
    import repro.analysis.hlo as new
    import repro.launch.hlo_tools as old

    for name in ("HloOp", "iter_ops", "ops_of_kind", "op_kinds",
                 "shape_bytes", "bytes_by_op_kind", "top_ops",
                 "top_collectives"):
        assert getattr(old, name) is getattr(new, name), name


def test_gather_bytes_for_paged_view_shape():
    """The PR 6 regression shape: a gather materializing the whole
    [B, W·ps, kv, hd] KV view must be measurable from the parsed op."""
    b, wps, kv, hd = 2, 40, 2, 16
    n = b * wps * kv * hd
    txt = f"  %g = f32[{b},{wps},{kv},{hd}]{{3,2,1,0}} gather(%pool, %idx)\n"
    (name, nbytes), = ops_of_kind(txt, "gather")
    assert name == "g" and nbytes == n * 4

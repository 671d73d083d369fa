"""Graph invariant passes: certify what the paper *claims* about the graph.

The headline claim is structural — DA replaces every weight multiply with
shift-and-add over stored weight-sums.  These passes prove the compiled
serving steps honor that contract instead of trusting the numerics tests:

* ``multiplier-free`` (jaxpr taint analysis): no float ``dot_general`` /
  ``convolution`` consumes a value on the weight datapath.  Weight leaves
  are taint sources; integer codes/LUTs taint ``INT_EXACT``, raw float
  weights taint ``FLOAT``.  A float dot over a ``FLOAT``-tainted operand
  is the multiplier the paper eliminated — flagged.  An ``INT_EXACT``
  operand may reach a float dot only when the *other* operand is a 0/1
  selector (a one-hot address row or an extracted bit-plane): that dot is
  an exact gather/shift-add in MXU clothing, the sanctioned DA trick.
  Anything else (e.g. dequantized codes fed to a real matmul) is flagged.
* ``no-big-gather`` (HLO): the PR-6 structural assert, generalized — no
  gather at (or above) the ``[B, W·ps, kv, hd]`` page-table view size in
  any fused-attention lowering, quantized-scale pools included.
* ``no-host-sync`` (HLO): the jitted step must not round-trip the host —
  no callbacks, infeed/outfeed, send/recv, or f64 escapes.
* ``dtype-discipline`` (HLO): softmax accumulates in f32 (no sub-f32
  ``exponential``); DA accumulators never silently widen past 32 bits.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis import hlo as hlo_mod
from repro.analysis.findings import Finding

#: Default allowlist: substrings matched against a finding's ``where``/
#: ``op``.  The bit-slicing *baseline* (``core/bitslice.py``) is the
#: paper's comparison datapath — it keeps conventional partial-product
#: multiplies by design, so its sites are exempt from ``multiplier-free``.
DEFAULT_ALLOWLIST: Tuple[str, ...] = ("bitslice_vmm", "core/bitslice.py")


# ---------------------------------------------------------------------------
# Taint lattice
# ---------------------------------------------------------------------------


class Flavor(enum.IntEnum):
    """Weight-datapath taint flavor, ordered for lattice joins."""

    NONE = 0        # not weight-derived (activations, indices, constants)
    INT_EXACT = 1   # integer weight codes / LUT sums, exact so far
    FLOAT = 2       # float weight values (raw or dequantized pre-reduce)


@dataclasses.dataclass(frozen=True)
class Taint:
    """Per-value state: weight flavor + is the value a 0/1 selector."""

    flavor: Flavor = Flavor.NONE
    selector: bool = False

    def join(self, other: "Taint") -> "Taint":
        return Taint(
            flavor=Flavor(max(self.flavor, other.flavor)),
            selector=self.selector and other.selector,
        )


UNTAINTED = Taint()
SELECTOR = Taint(flavor=Flavor.NONE, selector=True)


class _RefCell:
    """Mutable taint cell backing a Pallas ``Ref`` (monotone under join)."""

    __slots__ = ("taint",)

    def __init__(self, taint: Taint = UNTAINTED) -> None:
        self.taint = taint

    def join_in(self, t: Taint) -> bool:
        new = self.taint.join(t)  # monotone: the fixed point terminates
        changed = new != self.taint
        self.taint = new
        return changed


# Ops through which taint and selector-ness pass unchanged from the first
# (data) operand; trailing operands are indices/sizes.
_SHAPE_ONLY = {
    "reshape", "transpose", "squeeze", "expand_dims", "broadcast_in_dim",
    "slice", "dynamic_slice", "rev", "copy", "convert_element_type",
    "stop_gradient", "reduce_precision", "gather",
}
# Ops joining several data operands; selector survives iff all are selectors.
_JOIN_DATA = {"concatenate", "pad", "select_n", "select", "clamp",
              "dynamic_update_slice", "scatter", "scatter-add", "sort"}
# Bitwise / integer-exact arithmetic: flavor passes through.
_INT_EXACT_OK = {
    "add", "sub", "mul", "neg", "abs", "max", "min", "rem", "sign",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "right_shift", "and", "or", "xor", "not", "population_count",
    "clz", "dot_general_int",  # (marker; real dots handled separately)
}
# Comparisons: output is a fresh 0/1 selector, flavor drops.
_COMPARE = {"eq", "ne", "lt", "gt", "le", "ge"}
# Reductions that end a shift-add accumulation chain.
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "reduce"}
# Value-killing ops: outputs carry no weight information.
_FRESH = {"iota", "rng_bit_generator", "rng_uniform", "program_id",
          "num_programs", "create_token"}

_MF_PASS = "graph/multiplier-free"


def _is_float(aval: Any) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.floating)


def _is_ref(var: Any) -> bool:
    aval = getattr(var, "aval", None)
    return aval is not None and hasattr(aval, "inner_aval")


def _where(eqn: Any) -> str:
    # no try/except: a changed private API must fail loudly, not silently
    # empty every location the allowlist matches on
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ""
    return f"{frame.file_name}:{frame.start_line}"


def _literal_is_one(atom: Any) -> bool:
    val = getattr(atom, "val", None)
    if val is None:
        return False
    try:
        return bool(np.all(np.asarray(val) == 1))
    except Exception:
        return False


class _TaintInterpreter:
    """Abstract interpreter propagating weight taint through a jaxpr."""

    def __init__(self, findings: List[Finding], step_name: str) -> None:
        self.findings = findings
        self.step_name = step_name

    # -- environment ------------------------------------------------------

    def _read(self, env: Dict[Any, Any], atom: Any) -> Any:
        if not hasattr(atom, "aval") or type(atom).__name__ == "Literal":
            return UNTAINTED
        return env.get(atom, UNTAINTED)

    def _taint_of(self, env: Dict[Any, Any], atom: Any) -> Taint:
        val = self._read(env, atom)
        return val.taint if isinstance(val, _RefCell) else val

    # -- entry ------------------------------------------------------------

    def run(self, jaxpr: Any, args: Sequence[Any],
            consts: Sequence[Any] = ()) -> Tuple[List[Any], bool]:
        """Propagate through one jaxpr; returns (out values, changed)."""
        env: Dict[Any, Any] = {}
        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for var, val in zip(jaxpr.invars, args):
            env[var] = val
        changed = False
        for eqn in jaxpr.eqns:
            changed |= self._eqn(env, eqn)
        outs = [self._read(env, v) for v in jaxpr.outvars]
        return outs, changed

    # -- one equation -----------------------------------------------------

    def _eqn(self, env: Dict[Any, Any], eqn: Any) -> bool:
        prim = eqn.primitive.name
        handler = getattr(self, "_h_" + prim.replace("-", "_"), None)
        if handler is not None:
            return bool(handler(env, eqn))
        taints = [self._taint_of(env, a) for a in eqn.invars]
        out = self._default(prim, eqn, taints)
        for var in eqn.outvars:
            env[var] = out
        return False

    def _default(self, prim: str, eqn: Any, taints: List[Taint]) -> Taint:
        joined = UNTAINTED
        for t in taints:
            joined = Taint(Flavor(max(joined.flavor, t.flavor)), False)
        if prim in _FRESH:
            return UNTAINTED
        if prim in _COMPARE:
            return SELECTOR
        if prim in _SHAPE_ONLY:
            return taints[0] if taints else UNTAINTED
        if prim in _JOIN_DATA:
            sel = bool(taints) and all(
                t.selector or not t.flavor for t in taints
            ) and any(t.selector for t in taints)
            return Taint(joined.flavor, sel)
        if prim == "and" and any(_literal_is_one(a) for a in eqn.invars):
            # bit extraction: and(x >> b, 1) yields a 0/1 plane
            return Taint(joined.flavor, True)
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        if prim in _REDUCE:
            # accumulation endpoint: the shift-add chain terminates here;
            # what leaves is an inner-product value, not a weight
            return UNTAINTED
        if prim in _INT_EXACT_OK and out_aval is not None \
                and not _is_float(out_aval):
            sel = all(t.selector or not t.flavor for t in taints) and any(
                t.selector for t in taints
            )
            return Taint(joined.flavor, sel)
        if out_aval is not None and _is_float(out_aval) \
                and joined.flavor == Flavor.INT_EXACT:
            # float arithmetic on exact codes before any reduction: the
            # value is now a float weight surrogate (the dequantize-then-
            # matmul cheat) — escalate so a downstream dot flags it
            return Taint(Flavor.FLOAT, False)
        return joined

    # -- the check itself -------------------------------------------------

    def _check_dot(self, env: Dict[Any, Any], eqn: Any, kind: str) -> None:
        taints = [self._taint_of(env, a) for a in eqn.invars[:2]]
        out_aval = eqn.outvars[0].aval
        if not _is_float(out_aval) and not any(
            _is_float(a.aval) for a in eqn.invars[:2]
        ):
            return  # integer dot: shift-add by construction
        pair = list(zip(taints, reversed(taints)))
        for i, (mine, other) in enumerate(pair):
            side = "lhs" if i == 0 else "rhs"
            if mine.flavor == Flavor.FLOAT:
                self.findings.append(Finding(
                    pass_name=_MF_PASS, severity="error",
                    op=f"{kind}({side} float weight operand)",
                    hint="a float matmul consumes weight values — the "
                         "multiplier the paper eliminated; freeze the "
                         "layer (PackedWeights) or allowlist a baseline",
                    where=_where(eqn), step=self.step_name,
                ))
            elif mine.flavor == Flavor.INT_EXACT and not other.selector:
                self.findings.append(Finding(
                    pass_name=_MF_PASS, severity="error",
                    op=f"{kind}({side} integer weight codes x non-selector)",
                    hint="integer weight codes may meet a float dot only "
                         "against a 0/1 selector (one-hot LUT address or "
                         "extracted bit-plane); this operand is a general "
                         "float value — a real multiply over weights",
                    where=_where(eqn), step=self.step_name,
                ))

    def _h_dot_general(self, env: Dict[Any, Any], eqn: Any) -> bool:
        self._check_dot(env, eqn, "dot_general")
        for var in eqn.outvars:
            env[var] = UNTAINTED
        return False

    def _h_conv_general_dilated(self, env: Dict[Any, Any], eqn: Any) -> bool:
        self._check_dot(env, eqn, "convolution")
        for var in eqn.outvars:
            env[var] = UNTAINTED
        return False

    # -- higher-order primitives ------------------------------------------

    def _sub_jaxpr(self, params: Dict[str, Any]) -> Tuple[Any, List[Any]]:
        closed = params.get("jaxpr") or params.get("call_jaxpr")
        if closed is None:
            raise KeyError("no sub-jaxpr")
        if hasattr(closed, "jaxpr"):  # ClosedJaxpr
            return closed.jaxpr, [UNTAINTED] * len(closed.consts)
        return closed, []

    def _call_like(self, env: Dict[Any, Any], eqn: Any) -> bool:
        try:
            sub, consts = self._sub_jaxpr(eqn.params)
        except KeyError:
            for var in eqn.outvars:
                env[var] = UNTAINTED
            return False
        args = [self._read(env, a) for a in eqn.invars]
        outs, _ = self.run(sub, args, consts)
        for var, out in zip(eqn.outvars, outs):
            env[var] = out
        return False

    _h_pjit = _call_like
    _h_closed_call = _call_like
    _h_custom_jvp_call = _call_like
    _h_custom_vjp_call = _call_like
    _h_custom_vjp_call_jaxpr = _call_like
    _h_remat = _call_like
    _h_checkpoint = _call_like
    _h_core_call = _call_like
    _h_xla_call = _call_like

    def _h_cond(self, env: Dict[Any, Any], eqn: Any) -> bool:
        args = [self._read(env, a) for a in eqn.invars[1:]]
        outs: Optional[List[Any]] = None
        for branch in eqn.params["branches"]:
            b_outs, _ = self.run(
                branch.jaxpr, args, [UNTAINTED] * len(branch.consts)
            )
            if outs is None:
                outs = b_outs
            else:
                outs = [
                    o if isinstance(o, _RefCell) else o.join(
                        b.taint if isinstance(b, _RefCell) else b
                    )
                    for o, b in zip(outs, b_outs)
                ]
        for var, out in zip(eqn.outvars, outs or []):
            env[var] = out
        return False

    def _h_scan(self, env: Dict[Any, Any], eqn: Any) -> bool:
        params = eqn.params
        closed = params["jaxpr"]
        n_consts = params["num_consts"]
        n_carry = params["num_carry"]
        args = [self._read(env, a) for a in eqn.invars]
        consts, carry, xs = (
            args[:n_consts], args[n_consts:n_consts + n_carry],
            args[n_consts + n_carry:],
        )
        carry_t = [c.taint if isinstance(c, _RefCell) else c for c in carry]
        outs: List[Any] = []
        for _ in range(8):  # lattice height is tiny; convergence is fast
            outs, _ = self.run(
                closed.jaxpr, list(consts) + list(carry_t) + list(xs),
                [UNTAINTED] * len(closed.consts),
            )
            new_carry = [
                (o.taint if isinstance(o, _RefCell) else o).join(c)
                for o, c in zip(outs[:n_carry], carry_t)
            ]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        flat = list(carry_t) + [
            o.taint if isinstance(o, _RefCell) else o for o in outs[n_carry:]
        ]
        for var, out in zip(eqn.outvars, flat):
            env[var] = out
        return False

    def _h_while(self, env: Dict[Any, Any], eqn: Any) -> bool:
        params = eqn.params
        cond_n = params["cond_nconsts"]
        body_n = params["body_nconsts"]
        body = params["body_jaxpr"]
        args = [self._read(env, a) for a in eqn.invars]
        body_consts = args[cond_n:cond_n + body_n]
        carry = args[cond_n + body_n:]
        carry_t = [c.taint if isinstance(c, _RefCell) else c for c in carry]
        for _ in range(8):
            outs, _ = self.run(
                body.jaxpr, list(body_consts) + list(carry_t),
                [UNTAINTED] * len(body.consts),
            )
            new_carry = [
                (o.taint if isinstance(o, _RefCell) else o).join(c)
                for o, c in zip(outs, carry_t)
            ]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        for var, out in zip(eqn.outvars, carry_t):
            env[var] = out
        return False

    # -- Pallas kernels ----------------------------------------------------

    def _h_pallas_call(self, env: Dict[Any, Any], eqn: Any) -> bool:
        sub = eqn.params["jaxpr"]
        args = [self._read(env, a) for a in eqn.invars]
        n_out = len(eqn.outvars)
        cells: List[Any] = []
        for i, var in enumerate(sub.invars):
            if i < len(args):
                seed = args[i]
                seed_t = seed.taint if isinstance(seed, _RefCell) else seed
            else:
                seed_t = UNTAINTED
            cells.append(_RefCell(seed_t) if _is_ref(var) else seed_t)
        for _ in range(8):  # refs are monotone join cells: fixed point
            _, changed = self.run(sub, cells, [])
            if not changed:
                break
        # inner invars: [*outer operands (prefetch + inputs), *out refs,
        # *scratch refs] — outputs sit right after the operand block
        out_cells = cells[len(args):len(args) + n_out]
        for var, cell in zip(eqn.outvars, out_cells):
            env[var] = cell.taint if isinstance(cell, _RefCell) else UNTAINTED
        return False

    # -- Ref state primitives (inside Pallas bodies) -----------------------

    def _h_get(self, env: Dict[Any, Any], eqn: Any) -> bool:
        cell = self._read(env, eqn.invars[0])
        taint = cell.taint if isinstance(cell, _RefCell) else UNTAINTED
        for var in eqn.outvars:
            env[var] = taint
        return False

    def _h_swap(self, env: Dict[Any, Any], eqn: Any) -> bool:
        cell = self._read(env, eqn.invars[0])
        val = self._taint_of(env, eqn.invars[1])
        changed = False
        if isinstance(cell, _RefCell):
            changed = cell.join_in(val)
            for var in eqn.outvars:  # the joined view is the sound read
                env[var] = cell.taint
        else:
            for var in eqn.outvars:
                env[var] = val
        return changed

    def _h_addupdate(self, env: Dict[Any, Any], eqn: Any) -> bool:
        cell = self._read(env, eqn.invars[0])
        val = self._taint_of(env, eqn.invars[1])
        if isinstance(cell, _RefCell):
            return cell.join_in(val)
        return False


# ---------------------------------------------------------------------------
# Pass entry points
# ---------------------------------------------------------------------------


def multiplier_free(
    closed_jaxpr: Any,
    arg_taints: Sequence[Taint],
    step_name: str = "",
) -> List[Finding]:
    """Taint-check one traced step's jaxpr (allowlist applied by
    :func:`run_passes`)."""
    findings: List[Finding] = []
    interp = _TaintInterpreter(findings, step_name)
    jaxpr = closed_jaxpr.jaxpr
    args = list(arg_taints)
    if len(args) != len(jaxpr.invars):
        raise ValueError(
            f"{step_name}: {len(args)} arg taints for "
            f"{len(jaxpr.invars)} jaxpr inputs — seed taints with "
            "graph.arg_taints over the same flattened arguments"
        )
    interp.run(jaxpr, args, [UNTAINTED] * len(closed_jaxpr.consts))
    return findings


def no_big_gather(
    hlo_text: str,
    view_bytes: int,
    step_name: str = "",
) -> List[Finding]:
    """No gather at (or above) the re-materialized page-table KV view size
    — the op the fused Pallas page walk exists to remove."""
    findings: List[Finding] = []
    for name, nbytes in hlo_mod.ops_of_kind(hlo_text, "gather"):
        if nbytes >= view_bytes:
            findings.append(Finding(
                pass_name="graph/no-big-gather", severity="error",
                op=f"gather {name}", bytes=nbytes,
                hint=f"materializes >= the [B, W*ps, kv, hd] page-table "
                     f"view ({view_bytes} B) inside a fused-attention "
                     "lowering; the page walk must stay in-kernel",
                step=step_name,
            ))
    return findings


#: custom-call targets that stay on-device (accelerator kernels, sharding
#: annotations) — everything else is treated as a host round-trip.
_DEVICE_CUSTOM_CALLS = (
    "tpu_custom_call", "mosaic", "triton", "Sharding", "SPMD",
    "annotate_device_placement", "cu_threefry",
    # XLA's sort-free top-k kernel (MoE router lax.top_k lowers to it)
    "TopK",
)
_HOST_SYNC_KINDS = ("infeed", "outfeed", "send", "recv", "send-done",
                    "recv-done")


def no_host_sync(hlo_text: str, step_name: str = "") -> List[Finding]:
    """The jitted step must never synchronize with the host mid-step."""
    findings: List[Finding] = []
    for op in hlo_mod.iter_ops(hlo_text):
        if op.kind in _HOST_SYNC_KINDS:
            findings.append(Finding(
                pass_name="graph/no-host-sync", severity="error",
                op=f"{op.kind} {op.name}", bytes=op.result_bytes,
                hint="host transfer inside the jitted step stalls the "
                     "device every launch; stage data as arguments",
                step=step_name,
            ))
        elif op.kind == "custom-call":
            target = hlo_mod.custom_call_target(op)
            if any(tok in target for tok in ("callback", "python", "host")):
                findings.append(Finding(
                    pass_name="graph/no-host-sync", severity="error",
                    op=f"custom-call {op.name} target={target!r}",
                    bytes=op.result_bytes,
                    hint="a host callback in the hot path serializes every "
                         "step on the Python thread",
                    step=step_name,
                ))
            elif not any(tok in target for tok in _DEVICE_CUSTOM_CALLS):
                findings.append(Finding(
                    pass_name="graph/no-host-sync", severity="warning",
                    op=f"custom-call {op.name} target={target!r}",
                    bytes=op.result_bytes,
                    hint="unrecognized custom-call target; verify it stays "
                         "on-device (extend _DEVICE_CUSTOM_CALLS if so)",
                    step=step_name,
                ))
        elif op.kind == "convert" and op.type_str.startswith("f64"):
            findings.append(Finding(
                pass_name="graph/no-host-sync", severity="error",
                op=f"convert {op.name} -> {op.type_str}",
                bytes=op.result_bytes,
                hint="f64 escape in the step graph — usually a stray "
                     "Python float promoting the whole chain",
                step=step_name,
            ))
    return findings


def dtype_discipline(
    hlo_text: str,
    step_name: str = "",
    acc_bits: int = 32,
) -> List[Finding]:
    """Softmax accumulates in f32; DA accumulators stay within 32 bits."""
    findings: List[Finding] = []
    wide = {"s64", "u64", "f64"}
    for op in hlo_mod.iter_ops(hlo_text):
        dtypes = hlo_mod.shape_dtypes(op.type_str)
        if op.kind == "exponential" and dtypes & {"f16", "bf16"}:
            findings.append(Finding(
                pass_name="graph/dtype-discipline", severity="error",
                op=f"exponential {op.name} ({op.type_str})",
                bytes=op.result_bytes,
                hint="softmax must exponentiate/accumulate in f32 — "
                     "sub-f32 exp breaks the fused==gather bit-identity",
                step=step_name,
            ))
        elif op.kind in ("dot", "convolution") and dtypes & {"s64", "u64"}:
            findings.append(Finding(
                pass_name="graph/dtype-discipline", severity="error",
                op=f"{op.kind} {op.name} ({op.type_str})",
                bytes=op.result_bytes,
                hint=f"DA accumulator widened past acc_bits={acc_bits} "
                     "(64-bit dot) — the shift-add chain silently "
                     "outgrew its hardware accumulator",
                step=step_name,
            ))
        elif dtypes & wide and op.kind not in ("dot", "convolution"):
            findings.append(Finding(
                pass_name="graph/dtype-discipline", severity="error",
                op=f"{op.kind} {op.name} ({op.type_str})",
                bytes=op.result_bytes,
                hint="64-bit value in the step graph; the serving stack "
                     "is 32-bit end to end (jax x64 must stay off)",
                step=step_name,
            ))
    return findings


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


def apply_allowlist(
    findings: Sequence[Finding],
    allow: Sequence[str],
) -> List[Finding]:
    """Drop findings whose ``where``/``op`` matches an allowlist entry."""
    if not allow:
        return list(findings)
    return [
        f for f in findings
        if not any(tok in f.where or tok in f.op for tok in allow)
    ]


def run_passes(
    steps: Sequence[Any],
    allow: Sequence[str] = DEFAULT_ALLOWLIST,
    acc_bits: int = 32,
) -> List[Finding]:
    """Run the full pass pipeline over traced steps (see
    :func:`repro.analysis.graph.trace_serving_steps`)."""
    findings: List[Finding] = []
    for step in steps:
        findings += multiplier_free(
            step.closed_jaxpr, step.arg_taints, step_name=step.name
        )
        if step.hlo:
            if step.fused:
                findings += no_big_gather(
                    step.hlo, step.view_bytes, step_name=step.name
                )
            findings += no_host_sync(step.hlo, step_name=step.name)
            findings += dtype_discipline(
                step.hlo, step_name=step.name, acc_bits=acc_bits
            )
    return apply_allowlist(findings, allow)

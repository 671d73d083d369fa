"""Unified Distributed-Arithmetic execution engine (backend registry + dispatch).

The paper proves one identity — ``Y = X @ W`` computed multiplier-free via
precomputed weight-sum LUTs and bit-serial shift-and-add (§II–III) — and this
repo carries several equivalent executions of it: the faithful LUT gather, the
one-hot MXU readout, the storage-free bit-plane forms, and the Pallas TPU
kernels.  This module puts all of them behind ONE entry point::

    y = da_matmul(x, packed, mode="auto")          # float in → float out
    acc = da_vmm(xq, packed, mode="bitplane")      # integer codes → int32

with three pieces of machinery:

**1. The backend registry.**  Every execution mode is a :class:`BackendSpec`
registered under a canonical name with a *capability spec*: does it need
materialized LUTs?  What group sizes can it address?  Does it run on the int8
MXU path?  Does it handle K that is not a multiple of the group size (the
padding rule)?  ``registered_backends()`` is the single source of truth the
differential test suite sweeps, so a new backend is verified the moment it is
registered.

===================  =========  ======================================
name                 needs LUTs  execution
===================  =========  ======================================
``lut``              yes        faithful PMA readout: gather + shift-add
``onehot``           yes        one-hot(addr) @ LUT on the MXU
``pallas_lut``       yes        Pallas kernel (in-VMEM LUT readout)
``bitplane``         no         Σ_b 2^b · (xbit_b @ W), serial cycles
``bitplane_stacked`` no         bit-planes stacked on M: ONE int8 matmul
``pallas_bitplane``  no         Pallas kernel (bit-plane streaming)
``int8``             no         int8×int8 reference matmul (baseline,
                                not multiplier-free — never auto-picked)
===================  =========  ======================================

**2. The ``"auto"`` policy.**  ``mode="auto"`` picks the backend by one
rule, :func:`choose_backend`, from the activation rows M, the contraction K
and what the artifact carries: with LUTs and M ≤ 8 (decode-like) it reads
the PMAs (``lut``); otherwise it runs the one-matmul ``bitplane_stacked``
form.  Only capability-eligible DA backends are considered (LUT modes need
LUTs in the artifact; the ``int8`` baseline is never auto-picked because it
is not multiplier-free).  The freeze planner (:mod:`repro.core.freeze`)
applies the same rule at the expected decode batch, so a layer's planned
backend is the one call-time dispatch would pick for it.

**3. ``PackedWeights``.**  The single frozen-weight artifact: int8 codes +
per-column scale + optional LUTs, built ONCE by :func:`pack_weights` (the
paper's pre-VMM step, §III-A) and shared by every backend.  It is a pytree
(leaf names ``wq`` / ``w_scale`` / ``luts`` — stable for sharding rules), it
is callable (``packed(x)`` runs the engine), and MoE-style stacked experts
``[E, K, N]`` vmap through it unchanged.

This module is the *per-matrix* engine.  The **model-level** entry — walk a
params pytree, plan a backend/LUT decision per layer by the same rule, pack
every weight matrix, and serialize the result as a servable on-disk artifact
— is :mod:`repro.core.freeze` (plan → pack → serialize → shard → serve).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.da import (
    DAConfig,
    build_luts,
    da_vmm_bitplane,
    da_vmm_bitplane_stacked,
    da_vmm_lut,
    da_vmm_onehot,
    num_groups,
    truncate_codes,
)
from repro.core.quant import QTensor, quantize_acts_signed, quantize_weights

# ---------------------------------------------------------------------------
# PackedWeights — the one frozen-weight artifact every backend reads
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedWeights:
    """Frozen DA linear weights: the PMA contents for one weight matrix.

    wq:      [K, N] (or stacked experts [E, K, N]) integer codes, int8 storage.
    w_scale: [1, N] (or [E, 1, N]) per-output-column float32 scale.
    luts:    [G, 2^L, N] weight-sum tables from build_luts, or None.
    cfg:     DAConfig the artifact was packed under (group_size, x_bits).
    mode:    default execution mode for ``packed(x)`` ("auto" → dispatch).
    """

    wq: jax.Array
    w_scale: jax.Array
    luts: Optional[jax.Array]
    cfg: DAConfig
    mode: str = "auto"

    @property
    def k(self) -> int:
        return self.wq.shape[-2]

    @property
    def n(self) -> int:
        return self.wq.shape[-1]

    @property
    def has_luts(self) -> bool:
        return self.luts is not None

    def __call__(self, x: jax.Array) -> jax.Array:
        return da_matmul(x, self)  # mode=None → this artifact's default


jax.tree_util.register_pytree_with_keys(
    PackedWeights,
    lambda t: (
        (("wq", t.wq), ("w_scale", t.w_scale), ("luts", t.luts)),
        (t.cfg, t.mode),
    ),
    lambda aux, ch: PackedWeights(
        wq=ch[0], w_scale=ch[1], luts=ch[2], cfg=aux[0], mode=aux[1]
    ),
)


def lut_cells(k: int, n: int, group_size: int) -> int:
    """Memory cells a materialized LUT costs (the 2^L/L× blow-up, Table I)."""
    return num_groups(k, group_size) * (1 << group_size) * n


def path_entry_name(entry) -> str:
    """Canonical string for one pytree path entry (DictKey / GetAttrKey /
    SequenceKey / raw str key).  The single implementation shared by
    checkpoint keys, freeze plan keys and the sharding rules — serialized
    key paths must never drift between writers and readers."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


#: Default LUT budget in cells per matrix, shared by pack_weights and the
#: freeze planner — one constant decides which layers carry LUTs.
DEFAULT_LUT_LIMIT = 1 << 24


def pack_weights(
    w: jax.Array,
    cfg: DAConfig = DAConfig(x_signed=True),
    mode: str = "auto",
    lut_cell_limit: int = DEFAULT_LUT_LIMIT,
    with_luts: Optional[bool] = None,
) -> PackedWeights:
    """Pre-VMM procedure (§III-A): quantize once, sum weights, 'write the PMAs'.

    Accepts 2-D float weights [K, N] or batched experts [E, K, N].  LUTs are
    built exactly once, here, and shared by every LUT-reading backend:
    when ``mode`` names a LUT backend, or under ``mode="auto"`` whenever the
    blow-up stays within ``lut_cell_limit``.

    NOTE ``lut_cell_limit`` is measured in LUT **cells** per matrix (the paper's
    2^L/L× blow-up: ``lut_cells(k, n, group_size)``), not in weights — the
    seed's ``freeze_da`` bounded weight count instead; at group_size 8 one
    weight costs 32 cells, so the default 2^24 cells ≈ 64 MB of int32 LUTs
    admits layers up to ~512K weights.

    ``with_luts`` (when not None) overrides the LUT decision outright — the
    model-level planner (:mod:`repro.core.freeze`) decides lut-or-not per
    layer and passes its verdict down here.
    """
    mode = canonical_mode(mode)
    wq: QTensor = quantize_weights(w, bits=8, axis=w.ndim - 2)
    k, n = w.shape[-2], w.shape[-1]
    if with_luts is None:
        if mode == "auto":
            with_luts = lut_cells(k, n, cfg.group_size) <= lut_cell_limit
        else:
            with_luts = get_backend(mode).needs_luts
    luts = None
    if with_luts:
        build = partial(build_luts, group_size=cfg.group_size)
        for _ in range(w.ndim - 2):
            build = jax.vmap(build, in_axes=(0,), out_axes=0)
        luts = build(wq.q)
    # int8 storage: the codes are the deployable artifact (4× smaller reads)
    return PackedWeights(
        wq=wq.q.astype(jnp.int8), w_scale=wq.scale, luts=luts, cfg=cfg,
        mode=mode,
    )


def pack_quantized(
    wq: jax.Array,
    w_scale=1.0,
    cfg: DAConfig = DAConfig(),
    mode: str = "auto",
    with_luts: bool = True,
) -> PackedWeights:
    """Wrap already-integer weight codes [K, N] as a PackedWeights artifact."""
    mode = canonical_mode(mode)
    wq = jnp.asarray(wq)
    luts = build_luts(wq.astype(jnp.int32), cfg.group_size) if with_luts else None
    return PackedWeights(
        wq=wq, w_scale=jnp.asarray(w_scale, dtype=jnp.float32), luts=luts,
        cfg=cfg, mode=mode,
    )


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capability spec + implementation of one DA execution mode.

    fn:             (xq int32 [M,K], packed, cfg) → int32 [M,N] == xq @ wq.
    needs_luts:     reads materialized weight-sum LUTs from the artifact.
    is_da:          multiplier-free DA datapath (auto-dispatch only considers
                    these; baselines like int8 must be requested explicitly).
    int8_path:      contracts on the int8 MXU path (operands must fit int8).
    signed_only:    requires two's-complement activation codes.
    max_group_size: LUT addressability bound (2^L rows per PMA).
    pads_k:         handles K not a multiple of group_size by zero-padding.
    """

    name: str
    fn: Callable[[jax.Array, PackedWeights, DAConfig], jax.Array]
    description: str = ""
    needs_luts: bool = False
    is_da: bool = True
    #: Advisory, not an eligibility gate: the backend contracts on the int8
    #: MXU path (weight codes must fit int8 — guaranteed by the 8-bit
    #: quantizer). Drives TPU tiling choices.
    int8_path: bool = False
    signed_only: bool = False
    max_group_size: int = 16
    pads_k: bool = True

    def supports(self, cfg: DAConfig, has_luts: bool,
                 k: Optional[int] = None) -> bool:
        """Is this backend eligible for an artifact packed under ``cfg``?

        ``k`` (the contraction dim) is checked against the padding rule when
        known: a backend with ``pads_k=False`` only takes K that is a
        multiple of the group size."""
        if self.needs_luts and not has_luts:
            return False
        if self.signed_only and not cfg.x_signed:
            return False
        if cfg.group_size > self.max_group_size:
            return False
        if (k is not None and not self.pads_k
                and k % cfg.group_size != 0):
            return False
        return True


_REGISTRY: Dict[str, BackendSpec] = {}

#: Legacy / call-site mode spellings → canonical registry names.
MODE_ALIASES = {
    "da_lut": "lut",
    "da_onehot": "onehot",
    "da_bitplane": "bitplane",
    "da_bitplane_stacked": "bitplane_stacked",
    "stacked": "bitplane_stacked",
    "pallas": "pallas_lut",
}


def canonical_mode(mode: str) -> str:
    return MODE_ALIASES.get(mode, mode)


def register_backend(name: str, **caps):
    """Decorator: register ``fn(xq, packed, cfg) → int32`` under ``name``."""

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = BackendSpec(name=name, fn=fn, **caps)
        return fn

    return deco


def registered_backends() -> Dict[str, BackendSpec]:
    """Name → spec of every registered backend (the differential-test sweep)."""
    return dict(_REGISTRY)


def get_backend(mode: str) -> BackendSpec:
    name = canonical_mode(mode)
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown DA mode {mode!r}; registered backends: "
            f"{', '.join(sorted(_REGISTRY))} (plus 'auto' for shape-based "
            f"dispatch)"
        )
    return _REGISTRY[name]


@register_backend(
    "lut", needs_luts=True,
    description="faithful PMA readout: LUT gather + bit-serial shift-and-add",
)
def _lut_backend(xq, packed, cfg):
    return da_vmm_lut(xq, packed.luts, cfg)


@register_backend(
    "onehot", needs_luts=True,
    description="address decoder as one-hot; LUT readout on the MXU",
)
def _onehot_backend(xq, packed, cfg):
    return da_vmm_onehot(xq, packed.luts, cfg)


@register_backend(
    "pallas_lut", needs_luts=True,
    description="Pallas TPU kernel: in-VMEM LUT readout (interpret on CPU)",
)
def _pallas_lut_backend(xq, packed, cfg):
    from repro.kernels.ops import da_vmm as _kernel_da_vmm

    return _kernel_da_vmm(xq, packed.luts, cfg, backend="pallas")


@register_backend(
    "bitplane",
    description="storage-free serial DA: Σ_b 2^b · (xbit_b @ W)",
)
def _bitplane_backend(xq, packed, cfg):
    return da_vmm_bitplane(xq, packed.wq.astype(jnp.int32), cfg)


@register_backend(
    "bitplane_stacked", int8_path=True,
    description="bit-planes stacked on M: one int8 matmul, W read once",
)
def _stacked_backend(xq, packed, cfg):
    return da_vmm_bitplane_stacked(xq, packed.wq, cfg)


@register_backend(
    "pallas_bitplane",
    description="Pallas TPU kernel: bit-plane streaming (interpret on CPU)",
)
def _pallas_bitplane_backend(xq, packed, cfg):
    from repro.kernels.ops import bitplane_vmm as _kernel_bitplane_vmm

    # int8 storage passes through uncast: the kernel sizes its fp32-exact K
    # tile from the storage dtype (a pre-cast int32 tracer would hide it).
    return _kernel_bitplane_vmm(xq, packed.wq, cfg, backend="pallas")


@register_backend(
    "int8", is_da=False, int8_path=True, signed_only=True,
    description="int8×int8 reference matmul (quantization baseline, not DA)",
)
def _int8_backend(xq, packed, cfg):
    return jnp.matmul(
        xq.astype(jnp.int8), packed.wq.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )


def jit_backend(spec: BackendSpec, cfg: DAConfig):
    """jit-compiled ``fn(xq, packed)`` for one backend.  ``packed`` is a jit
    *argument*: closing over it would bake the (possibly multi-GB) LUT array
    into the compiled executable."""
    return jax.jit(lambda xq, p, _f=spec.fn: _f(xq, p, cfg))


# ---------------------------------------------------------------------------
# The "auto" policy: one rule from shape and artifact
# ---------------------------------------------------------------------------


def choose_backend(m: int, k: int, cfg: DAConfig, has_luts: bool) -> str:
    """The one backend rule, shared by call-time dispatch and the freeze
    planner: decode-like rows (M ≤ 8) read the PMAs when the artifact
    carries LUTs; everything else runs the one-matmul stacked bit-plane form
    (W read once — the TPU-shaped mapping).  Only capability-eligible DA
    backends are considered; if neither named one is eligible, the first
    eligible name wins."""
    names = sorted(
        s.name for s in _REGISTRY.values()
        if s.is_da and s.supports(cfg, has_luts, k=k)
    )
    if not names:  # unreachable with the built-in backends, but be loud
        raise ValueError(
            f"no DA backend supports cfg={cfg} has_luts={has_luts} k={k}"
        )
    if has_luts and m <= 8 and "lut" in names:
        return "lut"
    if "bitplane_stacked" in names:
        return "bitplane_stacked"
    return names[0]


def select_backend(
    m: int, k: int, n: int, cfg: DAConfig, has_luts: bool = True
) -> str:
    """Call-time ``"auto"`` dispatch of an [M, K] x [K, N] product: the rule
    of :func:`choose_backend` (N does not enter it).  Always returns a
    registered, eligible DA backend name."""
    return choose_backend(m, k, cfg, has_luts)


# ---------------------------------------------------------------------------
# Execution entry points
# ---------------------------------------------------------------------------

#: Process-wide draft precision (see :func:`x_bits_override`); None → full.
_X_BITS_EFF: Optional[int] = None


@contextlib.contextmanager
def x_bits_override(x_bits_eff: Optional[int]):
    """Trace-time partial-precision context (the DA-native draft pass).

    Inside this context every :func:`da_matmul` / :func:`da_vmm` call that
    does not pass an explicit ``x_bits_eff`` evaluates only the top
    ``x_bits_eff`` bit-planes of its activations against the *same* packed
    weights — no second model, no extra weight memory (see
    :func:`repro.core.da.truncate_codes`).  The override is read at **trace
    time**: wrap the function body you hand to ``jax.jit`` (a distinct
    callable per precision), not the call of an already-compiled function.
    ``None`` restores full precision.  This is what the speculative-decoding
    subsystem's truncated-bitplane self-draft provider uses to run a whole
    model forward at draft precision without threading a parameter through
    every layer.
    """
    global _X_BITS_EFF
    prev = _X_BITS_EFF
    _X_BITS_EFF = x_bits_eff
    try:
        yield
    finally:
        _X_BITS_EFF = prev


def effective_x_bits(cfg: DAConfig, x_bits_eff: Optional[int]) -> int:
    """Resolve a call-site ``x_bits_eff`` against the override context and
    the packed config; validates the range."""
    eff = x_bits_eff if x_bits_eff is not None else _X_BITS_EFF
    if eff is None:
        return cfg.x_bits
    eff = min(int(eff), cfg.x_bits)
    if eff < 1:
        raise ValueError(f"x_bits_eff={eff} must be >= 1")
    return eff


def _resolve_spec(
    mode: Optional[str], m: int, k: int, n: int, cfg: DAConfig, has_luts: bool,
    default_mode: str,
) -> BackendSpec:
    """Resolve a call-site mode to a backend spec, enforcing capabilities.

    ``None`` defers to the artifact's packed default; ``"auto"`` always runs
    shape-based dispatch (even on artifacts packed with a concrete mode).
    Explicit modes are checked against the backend's capability spec so a
    mismatch errors instead of silently computing wrong integers (e.g. the
    int8 baseline fed unsigned 8-bit codes would wrap at 128).
    """
    mode = canonical_mode(default_mode if mode is None else mode)
    if mode == "auto":
        return _REGISTRY[select_backend(m, k, n, cfg, has_luts)]
    spec = get_backend(mode)
    if not spec.supports(cfg, has_luts, k=k):
        why = (
            "reads materialized LUTs but the PackedWeights artifact has none"
            " — pack with a LUT mode or raise lut_cell_limit"
            if spec.needs_luts and not has_luts
            else "requires two's-complement (signed) activation codes"
            if spec.signed_only and not cfg.x_signed
            else f"supports group_size ≤ {spec.max_group_size}, got "
            f"{cfg.group_size}"
            if cfg.group_size > spec.max_group_size
            else f"does not pad K: {k} is not a multiple of group_size "
            f"{cfg.group_size}"
        )
        raise ValueError(f"backend {mode!r} {why}")
    return spec


def _check_lut_shape(spec: BackendSpec, packed: PackedWeights,
                     cfg: DAConfig) -> None:
    """A cfg override whose group_size disagrees with the packed LUTs would
    silently gather wrong rows (addresses clamp/broadcast) — error instead."""
    if spec.needs_luts and packed.luts.shape[-2] != (1 << cfg.group_size):
        raise ValueError(
            f"backend {spec.name!r}: LUTs were packed with "
            f"{packed.luts.shape[-2]} rows per PMA but cfg.group_size="
            f"{cfg.group_size} addresses {1 << cfg.group_size} — repack the "
            f"weights or use the packed cfg"
        )


def da_vmm(
    xq: jax.Array, packed: PackedWeights, mode: Optional[str] = None,
    cfg: Optional[DAConfig] = None, x_bits_eff: Optional[int] = None,
) -> jax.Array:
    """Integer-level engine entry: int codes [.., K] → int32 [.., N] == xq @ wq.

    ``mode``: None → the artifact's packed default; ``"auto"`` → shape-based
    dispatch; otherwise a registered backend name (capability-checked).
    ``cfg`` overrides the packed config (e.g. to flip x_signed for unsigned
    image inputs); group_size must match the packed LUTs.

    ``x_bits_eff < cfg.x_bits`` evaluates only the top bit-planes (fewer
    bit-serial cycles against the same artifact — the draft pass); defaults
    to the :func:`x_bits_override` context, else full precision.
    """
    cfg = cfg if cfg is not None else packed.cfg
    eff = effective_x_bits(cfg, x_bits_eff)
    ecfg = dataclasses.replace(cfg, x_bits=eff)
    m = 1
    for d in xq.shape[:-1]:
        m *= int(d)
    spec = _resolve_spec(mode, m, packed.k, packed.n, ecfg, packed.has_luts,
                         default_mode=packed.mode)
    _check_lut_shape(spec, packed, ecfg)
    lead = xq.shape[:-1]
    x2 = xq.reshape(-1, xq.shape[-1]).astype(jnp.int32)
    x2, rcfg, drop = truncate_codes(x2, cfg, eff)
    acc = spec.fn(x2, packed, rcfg)
    if drop:
        acc = acc * (1 << drop)
    return acc.reshape(lead + (packed.n,))


@partial(jax.jit, static_argnames=("cfg", "backend", "x_bits_eff"))
def _da_matmul_jit(x2, packed, cfg, backend, x_bits_eff):
    # named_scope stamps the backend into the HLO metadata, so an XLA
    # profiler capture attributes device time to the DA backend that spent it
    with jax.named_scope(f"da_{backend}"):
        xqt = quantize_acts_signed(x2, bits=cfg.x_bits)
        xq, rcfg, drop = truncate_codes(xqt.q, cfg, x_bits_eff)
        acc = _REGISTRY[backend].fn(xq, packed, rcfg)
        if drop:
            acc = acc * (1 << drop)
        return acc.astype(jnp.float32) * xqt.scale * packed.w_scale


def da_matmul(
    x: jax.Array,
    weights: PackedWeights,
    cfg: Optional[DAConfig] = None,
    mode: Optional[str] = None,
    x_bits_eff: Optional[int] = None,
) -> jax.Array:
    """Float-level engine entry: quantize → DA integer VMM → dequantize.

    x: [.., K] float; weights: a PackedWeights artifact.  ``mode``: None →
    the artifact's packed default; ``"auto"`` → shape-based dispatch (always,
    even on artifacts packed with a concrete mode); otherwise a registered
    backend name or legacy alias (capability-checked).  Activations are
    dynamically quantized to signed ``x_bits``.

    ``x_bits_eff < cfg.x_bits`` truncates the quantized codes to their top
    bit-planes before the integer VMM (same scale, same weights, fewer DA
    cycles) — the truncated-bitplane draft pass.  Defaults to the
    :func:`x_bits_override` context, else full precision.
    """
    cfg = cfg if cfg is not None else weights.cfg
    scfg = dataclasses.replace(cfg, x_signed=True)
    eff = effective_x_bits(scfg, x_bits_eff)
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= int(d)
    rcfg = dataclasses.replace(scfg, x_bits=eff)  # dispatch sees draft cycles
    spec = _resolve_spec(mode, m, weights.k, weights.n, rcfg,
                         weights.has_luts, default_mode=weights.mode)
    _check_lut_shape(spec, weights, rcfg)
    x2 = x.reshape(-1, k).astype(jnp.float32)
    y = _da_matmul_jit(x2, weights, scfg, spec.name, eff)
    return y.reshape(lead + (weights.n,))


def dense(x: jax.Array, w) -> jax.Array:
    """Weight application dispatching on the leaf type: a plain array is a
    float matmul (training); a PackedWeights artifact runs the paper's
    multiplier-free datapath through the engine (serving).  MoE-style stacked
    experts ([E, K, N] against [E, C, K]) vmap the whole artifact per expert —
    codes, scales and LUTs alike (None LUTs contribute no leaves)."""
    if isinstance(w, PackedWeights):
        if w.wq.ndim == 3:  # per-expert PMAs
            if x.ndim == 4:  # grouped MoE activations [G, E, C, D]
                return jax.vmap(lambda xg: dense(xg, w))(x)
            assert x.ndim == 3, x.shape
            return jax.vmap(lambda xe, we: we(xe))(x, w).astype(x.dtype)
        return w(x).astype(x.dtype)
    if w.ndim == 3 and x.ndim == 4:
        return jnp.einsum("gecd,edf->gecf", x, w)
    if w.ndim == 3 and x.ndim == 3:
        return jnp.einsum("ecd,edf->ecf", x, w)
    return x @ w


# ---------------------------------------------------------------------------
# Paged-attention read backends — the decode-attention analogue of the DA
# registry.  The paged runtime has two interchangeable executions of the same
# attention read over the page pool; "auto" picks one by platform.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnBackendSpec:
    """One execution of the paged-attention read.

    fn: ``(q [B,T,H,hd], k_pool, v_pool [(L,)P,ps,kv,hd], page_table [B,W],
    tpos [B,T], *, softmax_dtype, mask_mode, k_scale=None, v_scale=None,
    layer=None) → [B,T,H,hd]`` — the attention context over an
    already-written pool, ragged-masked by ``tpos``; a period-stacked 5-D
    pool is read at ``layer``.  Quantized pools (int8 codes / packed int4)
    pass their in-page dequant scales ``[(L,)P, ps, kv, 1]``; both backends
    dequantize with the same elementwise formula (``kv_quant``), so they
    attend over bitwise the same keys and values on quantized pages too.
    """

    name: str
    fn: Callable[..., jax.Array]
    description: str = ""


_ATTN_REGISTRY: Dict[str, AttnBackendSpec] = {}


def register_attn_backend(name: str, **caps):
    """Decorator: register a paged-attention read under ``name``."""

    def deco(fn):
        if name in _ATTN_REGISTRY:
            raise ValueError(f"attention backend {name!r} already registered")
        _ATTN_REGISTRY[name] = AttnBackendSpec(name=name, fn=fn, **caps)
        return fn

    return deco


def registered_attn_backends() -> Dict[str, AttnBackendSpec]:
    """Name → spec of every paged-attention read (differential-test sweep)."""
    return dict(_ATTN_REGISTRY)


def get_attn_backend(mode: str) -> AttnBackendSpec:
    if mode not in _ATTN_REGISTRY:
        raise ValueError(
            f"unknown paged-attention backend {mode!r}; registered: "
            f"{', '.join(sorted(_ATTN_REGISTRY))} (plus 'auto' for "
            "platform dispatch)"
        )
    return _ATTN_REGISTRY[mode]


def select_attn_backend(mode: Optional[str]) -> str:
    """Resolve a ``cfg.paged_attn`` mode to a registered backend name.

    ``"auto"`` (and None) is the fused Pallas page walk on TPU and the XLA
    gather read elsewhere (off-TPU the kernel runs in interpreter mode, a
    correctness tool rather than a fast path)."""
    if mode is None or mode == "auto":
        return "fused" if jax.default_backend() == "tpu" else "gather"
    return get_attn_backend(mode).name


@register_attn_backend(
    "gather",
    description="XLA read: page-table gather to [B,S,kv,hd] + masked softmax",
)
def _gather_attn_backend(q, k_pool, v_pool, page_table, tpos, **kw):
    from repro.models.attention import paged_gather_read

    return paged_gather_read(q, k_pool, v_pool, page_table, tpos, **kw)


@register_attn_backend(
    "fused",
    description="Pallas kernel: walk of each row's live key blocks, online "
    "softmax per KV head (interpret off-TPU)",
)
def _fused_attn_backend(q, k_pool, v_pool, page_table, tpos, **kw):
    from repro.kernels.paged_attention import paged_attention

    return paged_attention(q, k_pool, v_pool, page_table, tpos, **kw)


# ---------------------------------------------------------------------------
# Fused QKV projection — one DA pass per layer over three PackedWeights
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "backends", "x_bits_eff", "splits"))
def _da_qkv_jit(x2, packs, cfg, backends, x_bits_eff, splits):
    # backend set in the HLO metadata → profiler attributes the fused pass
    with jax.named_scope("da_qkv_" + "_".join(dict.fromkeys(backends))):
        return _da_qkv_impl(x2, packs, cfg, backends, x_bits_eff, splits)


def _da_qkv_impl(x2, packs, cfg, backends, x_bits_eff, splits):
    xqt = quantize_acts_signed(x2, bits=cfg.x_bits)
    xq, rcfg, drop = truncate_codes(xqt.q, cfg, x_bits_eff)
    if len(set(backends)) == 1 and not _REGISTRY[backends[0]].needs_luts:
        # One storage-free backend serves all three: concatenate the code
        # matrices on N and run ONE integer VMM.  Each output column is an
        # independent exact integer dot, so the split accumulators are the
        # very integers three separate calls would produce.
        merged = PackedWeights(
            wq=jnp.concatenate([p.wq for p in packs], axis=-1),
            w_scale=jnp.ones((1, 1), jnp.float32), luts=None,
            cfg=cfg, mode=backends[0],
        )
        accs = jnp.split(_REGISTRY[backends[0]].fn(xq, merged, rcfg),
                         list(splits), axis=-1)
    else:
        accs = [_REGISTRY[b].fn(xq, p, rcfg) for b, p in zip(backends, packs)]
    outs = []
    for acc, p in zip(accs, packs):
        if drop:
            acc = acc * (1 << drop)
        outs.append(acc.astype(jnp.float32) * xqt.scale * p.w_scale)
    return tuple(outs)


def da_qkv_matmul(
    x: jax.Array,
    packs,
    cfg: Optional[DAConfig] = None,
    mode: Optional[str] = None,
    x_bits_eff: Optional[int] = None,
):
    """Fused multi-head projection: one DA pass over several PackedWeights.

    ``x [.., K]`` against ``packs`` (e.g. the q/k/v artifacts of one layer,
    all packed under one DAConfig with the same K).  The activations are
    quantized and bit-plane-decomposed ONCE, and when every matrix resolves
    to the same storage-free backend the three VMMs run as a single
    concatenated pass — the weights stream through the datapath once per
    decode step instead of three times.  Outputs are BIT-IDENTICAL to
    separate :func:`da_matmul` calls: shared quantization is the same
    quantization, the integer backends are exact, and dequantization is
    per-column.  Returns a tuple of ``[.., N_i]`` float arrays.

    ``x_bits_eff`` / the :func:`x_bits_override` context truncate the shared
    codes exactly as in :func:`da_matmul` (the draft pass fuses too).
    """
    packs = tuple(packs)
    if not packs:
        raise ValueError("da_qkv_matmul needs at least one PackedWeights")
    base = cfg if cfg is not None else packs[0].cfg
    for p in packs:
        if not isinstance(p, PackedWeights) or p.wq.ndim != 2:
            raise ValueError("da_qkv_matmul fuses 2-D PackedWeights only")
        if cfg is None and p.cfg != base:
            raise ValueError(
                "da_qkv_matmul: packs disagree on DAConfig — pass cfg= to "
                "override, or fall back to separate da_matmul calls"
            )
        if p.k != packs[0].k:
            raise ValueError(
                f"da_qkv_matmul: contraction dims differ ({p.k} vs "
                f"{packs[0].k})"
            )
    scfg = dataclasses.replace(base, x_signed=True)
    eff = effective_x_bits(scfg, x_bits_eff)
    rcfg = dataclasses.replace(scfg, x_bits=eff)  # dispatch sees draft cycles
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= int(d)
    backends = []
    for p in packs:
        spec = _resolve_spec(mode, m, p.k, p.n, rcfg, p.has_luts,
                             default_mode=p.mode)
        _check_lut_shape(spec, p, rcfg)
        backends.append(spec.name)
    splits = []
    for p in packs[:-1]:
        splits.append((splits[-1] if splits else 0) + p.n)
    x2 = x.reshape(-1, k).astype(jnp.float32)
    ys = _da_qkv_jit(x2, packs, scfg, tuple(backends), eff, tuple(splits))
    return tuple(y.reshape(lead + (p.n,)) for y, p in zip(ys, packs))

"""Model-level DA freeze: plan → pack → serialize → shard → serve.

The paper's premise is that the weight matrix is *constant* (§II-A): all the
expensive work — quantizing weights and precomputing the weight-sum LUTs (the
PMA contents, §III-A) — happens once, offline, and inference is shift-and-add
readout.  This module makes that premise operational at model scale:

1. **Plan** (:func:`plan_model`): for every weight-matrix leaf of a params
   pytree, decide lut-or-not from the layer's (K, N) shape against the LUT
   cell budget, then pick its backend by the engine's one rule
   (:func:`repro.core.engine.choose_backend`) at the expected decode batch —
   the same rule ``mode="auto"`` dispatch applies at call time.
2. **Pack** (:func:`freeze_model`): run the pre-VMM step per leaf under its
   plan, producing a :class:`DAArtifact` — the packed params pytree plus the
   plan, DA config, and (optionally) the model config.
3. **Serialize** (:func:`save_artifact` / :func:`load_artifact`): persist the
   artifact via the checkpoint layer (crc-checked arrays, DAConfig + plan in
   the manifest) so a serving process boots from disk with **zero float
   weights and zero re-packing** — see ``ServeEngine.from_artifact`` and
   ``examples/serve_da.py --artifact``.
4. **Shard**: packed leaf names (``wq`` / ``w_scale`` / ``luts``) have
   sharding rules in :mod:`repro.launch.sharding`
   (``shard_frozen_params``) — a frozen model tensor-parallels its PMAs
   across the mesh like any other param.

Routers, norms, biases, embeddings and scalar SSM params stay float: they are
not VMMs (gather / elementwise).
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Any, Dict, Optional

import jax

from repro.core.da import DAConfig
from repro.core.engine import (
    DEFAULT_LUT_LIMIT,
    PackedWeights,
    canonical_mode,
    choose_backend,
    get_backend,
    lut_cells,
    pack_weights,
    path_entry_name,
    registered_backends,
)


def _hwcost():
    """Deferred import: ``repro.obs.hwcost`` imports ``core.hwmodel``,
    and importing the ``repro.core`` package imports this module — a
    module-level import would be circular whenever ``obs.hwcost`` is
    the first thing a process imports."""
    from repro.obs import hwcost

    return hwcost

#: Artifact schema version — bumped on any layout/manifest change.
ARTIFACT_VERSION = 1
ARTIFACT_FORMAT = "da-artifact"

# Param leaf names that are weight matrices (x @ W shaped [in, out] or
# batched expert weights [E, in, out]).
DA_LEAF_NAMES = {
    "wq", "wk", "wv", "wo",          # attention projections
    "w_up", "w_gate", "w_down",      # MLP / MoE experts / shared experts
    "in_proj", "out_proj",           # mamba projections
    "w",                             # lm head
}
SKIP_CONTEXT = {"router", "conv_w", "table"}

_SEP = "/"


# ---------------------------------------------------------------------------
# Plan schema
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's freeze decision: what to pack and how to execute it.

    mode:        concrete backend name this layer serves under.
    group_size:  rows per PMA for this layer (LUT addressability).
    with_luts:   materialize the weight-sum LUTs (the PMA write) or not.
    k, n:        the weight matrix shape the plan was made for.
    source:      "rule" (planned by :func:`~repro.core.engine.choose_backend`),
                 "pinned" (a concrete mode was requested, no planning) or
                 "stale" (planned for a backend this build does not
                 register; demoted to ``mode="auto"`` on load).  Manifests
                 written by older builds keep the string they recorded.
    kv_dtype:    KV-page precision this layer's cache serves at (recorded on
                 the wk/wv mixer leaves only — ``"fp16"`` | ``"int8"`` |
                 ``"int4"``; None for every non-KV leaf).  Artifacts carry it
                 so ``ServeEngine.from_artifact`` builds a pool matching the
                 plan instead of silently defaulting.
    """

    mode: str
    group_size: int
    with_luts: bool
    k: int
    n: int
    source: str = "rule"
    kv_dtype: Optional[str] = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "LayerPlan":
        d = dict(d)
        d.pop("est_cost", None)  # written by older builds' cost planner
        return cls(**d)


@dataclasses.dataclass
class DAArtifact:
    """The frozen, servable model: packed params + the plan that shaped them.

    params:    pytree with :class:`PackedWeights` at every weight-matrix leaf
               (non-VMM leaves stay float).
    plan:      leaf path (``periods/pos_0/mixer/wq``) → :class:`LayerPlan`.
    da_cfg:    DAConfig the model was frozen under (each PackedWeights also
               carries its own cfg).
    model_cfg: the ModelConfig needed to rebuild the serving graph, or None
               for bare trees (round-tripped through the manifest).
    hwcost:    :class:`~repro.obs.hwcost.HardwareCostModel` pricing every
               packed leaf on the paper's DA circuits (and the bit-slicing
               counterfactual).  Built at freeze time, carried in the
               manifest, rebuilt from the packed params when loading older
               artifacts that predate it.
    """

    params: Any
    plan: Dict[str, LayerPlan]
    da_cfg: DAConfig
    model_cfg: Any = None
    version: int = ARTIFACT_VERSION
    hwcost: Optional["HardwareCostModel"] = None
    #: latest ``repro.analysis.check`` verdict recorded against this artifact
    #: on disk (via :func:`record_analysis`), or None when never checked
    analysis: Optional[Dict[str, Any]] = None


# ---------------------------------------------------------------------------
# The planner: the engine's backend rule at the expected decode batch
# ---------------------------------------------------------------------------


def plan_layer(
    k: int,
    n: int,
    da_cfg: DAConfig,
    m_hint: int = 4,
    lut_cell_limit: int = DEFAULT_LUT_LIMIT,
) -> LayerPlan:
    """Choose (mode, lut-or-not) for one K×N weight matrix.

    LUTs are written when their cells at the base group size fit
    ``lut_cell_limit``; the backend is :func:`choose_backend` at
    ``m_hint``, the expected serving batch (decode M) — what ``mode="auto"``
    dispatch picks for a decode call against the packed layer.
    """
    with_luts = lut_cells(k, n, da_cfg.group_size) <= lut_cell_limit
    return LayerPlan(mode=choose_backend(m_hint, k, da_cfg, with_luts),
                     group_size=da_cfg.group_size, with_luts=with_luts,
                     k=k, n=n)


def _path_key(path) -> str:
    return _SEP.join(path_entry_name(p) for p in path)


def _is_da_leaf(path, leaf) -> bool:
    names = [path_entry_name(p) for p in path]
    if not names or any(n in SKIP_CONTEXT for n in names):
        return False  # router / conv / embedding subtrees stay float
    return (names[-1] in DA_LEAF_NAMES
            and hasattr(leaf, "ndim") and leaf.ndim >= 2)


def plan_model(
    params: Any,
    da_cfg: DAConfig = DAConfig(x_signed=True),
    m_hint: int = 4,
    lut_cell_limit: int = DEFAULT_LUT_LIMIT,
) -> Dict[str, LayerPlan]:
    """Per-layer plans for every weight-matrix leaf of ``params`` (no packing).

    Leaves stacked over periods/experts ([P, K, N] / [P, E, K, N]) get one
    plan from their trailing (K, N) — every period shares the layer shape.
    """
    plans: Dict[str, LayerPlan] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        if _is_da_leaf(path, leaf):
            plans[_path_key(path)] = plan_layer(
                int(leaf.shape[-2]), int(leaf.shape[-1]), da_cfg,
                m_hint=m_hint, lut_cell_limit=lut_cell_limit,
            )
    return plans


# ---------------------------------------------------------------------------
# Freeze: pack every planned leaf
# ---------------------------------------------------------------------------


def freeze_model(
    params: Any,
    da_cfg: DAConfig = DAConfig(x_signed=True),
    mode: str = "auto",
    m_hint: int = 4,
    lut_cell_limit: int = DEFAULT_LUT_LIMIT,
    model_cfg: Any = None,
    pin_modes: bool = True,
    kv_dtype_overrides: Optional[Dict[str, str]] = None,
) -> DAArtifact:
    """Walk the param tree; pack every weight leaf under its per-layer plan.

    ``mode="auto"`` runs the planner (:func:`plan_layer`).  A concrete
    ``mode`` (any registered backend, legacy ``da_*`` spellings accepted)
    pins every layer to it — the one-size-fits-all escape hatch.

    When ``model_cfg`` is given, the plan's wk/wv mixer entries additionally
    record the KV-page precision their cache serves at:
    ``model_cfg.kv_dtype`` globally, overridable per layer position via
    ``kv_dtype_overrides`` (``{"pos_i": "fp16"|"int8"|"int4"}`` — the
    per-layer escape hatch).  The artifact manifest then carries the KV
    precision alongside every DA packing decision, so a serving process
    booting ``from_artifact`` builds a matching pool or fails loudly.

    ``pin_modes=True`` bakes each layer's planned backend into its
    ``PackedWeights`` default, so serving needs no dispatch machinery (and a
    cold process reproduces the planner's choices exactly); LUTs are then
    only materialized when the pinned backend actually reads them.
    ``pin_modes=False`` packs per the plan but leaves ``mode="auto"`` for
    runtime shape dispatch (prefill and decode may then use different
    backends on the same artifact), keeping every feasible LUT.
    """
    mode = canonical_mode(mode)
    planned = mode == "auto"
    plans: Dict[str, LayerPlan] = {}
    base_kv = getattr(model_cfg, "kv_dtype", None) if model_cfg else None
    for key, dt in (kv_dtype_overrides or {}).items():
        from repro.models.kv_quant import KV_DTYPES

        if dt not in KV_DTYPES:
            raise ValueError(f"kv_dtype_overrides[{key!r}]={dt!r}; expected "
                             f"one of {KV_DTYPES}")

    def walk(path, leaf):
        if not _is_da_leaf(path, leaf):
            return leaf
        k, n = int(leaf.shape[-2]), int(leaf.shape[-1])
        if planned:
            plan = plan_layer(k, n, da_cfg, m_hint=m_hint,
                              lut_cell_limit=lut_cell_limit)
            if pin_modes and not get_backend(plan.mode).needs_luts:
                # The pinned backend never reads PMAs: materializing them
                # would write up to 2^L/L× dead cells into every artifact.
                # (Un-pinned artifacts keep feasible LUTs — runtime dispatch
                # may still pick a LUT backend at other shapes.)
                plan = dataclasses.replace(plan, with_luts=False)
        else:
            plan = LayerPlan(
                mode=mode, group_size=da_cfg.group_size,
                with_luts=get_backend(mode).needs_luts, k=k, n=n,
                source="pinned",
            )
        names = [path_entry_name(p) for p in path]
        if base_kv is not None and names[-1] in ("wk", "wv"):
            pos_seg = next((s for s in names if s.startswith("pos_")), None)
            plan = dataclasses.replace(
                plan,
                kv_dtype=(kv_dtype_overrides or {}).get(pos_seg, base_kv))
        plans[_path_key(path)] = plan
        return pack_weights(
            leaf, da_cfg,
            mode=plan.mode if (pin_modes or not planned) else "auto",
            with_luts=plan.with_luts,
        )

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    packed = jax.tree_util.tree_unflatten(
        treedef, [walk(path, leaf) for path, leaf in flat]
    )
    return DAArtifact(params=packed, plan=plans, da_cfg=da_cfg,
                      model_cfg=model_cfg,
                      hwcost=_hwcost().HardwareCostModel.from_frozen(
                          packed, plans))


def freeze_model_da(
    params: Any,
    da_cfg: DAConfig = DAConfig(x_signed=True),
    mode: str = "auto",
    lut_cell_limit: int = 1 << 24,
) -> Any:
    """Legacy surface: freeze and return only the packed params pytree."""
    return freeze_model(params, da_cfg, mode=mode,
                        lut_cell_limit=lut_cell_limit).params


# ---------------------------------------------------------------------------
# Serialize / load (the serve-many half of freeze-once)
# ---------------------------------------------------------------------------


def save_artifact(directory: str, artifact: DAArtifact) -> str:
    """Persist a DAArtifact: ``<dir>/arrays.npz`` + ``manifest.json``.

    Atomic (write to ``<dir>.tmp``, fsync, rename) and crc-checked per array
    via the checkpoint layer; the manifest carries the DA config, the full
    per-layer plan and the model config.
    """
    from repro.checkpoint import ckpt

    extra = {
        "format": ARTIFACT_FORMAT,
        "artifact_version": artifact.version,
        "da_cfg": dataclasses.asdict(artifact.da_cfg),
        "plan": {k: p.to_json() for k, p in artifact.plan.items()},
    }
    if artifact.hwcost:
        extra["hwcost"] = artifact.hwcost.to_json()
    if artifact.model_cfg is not None:
        extra["model_cfg"] = dataclasses.asdict(artifact.model_cfg)
    return ckpt.save_tree(directory, artifact.params, extra_manifest=extra)


def load_artifact(directory: str) -> DAArtifact:
    """Boot a DAArtifact from disk: no float weights, no re-packing.

    The packed params are reconstructed template-free (the manifest records
    which paths are PackedWeights and their DAConfig/mode), arrays are
    crc-verified, and each layer's planned mode is validated against the
    live backend registry — a plan naming a backend that no longer exists
    degrades that layer to ``mode="auto"`` with a warning instead of raising
    ``KeyError`` at dispatch time.
    """
    from repro.checkpoint import ckpt

    with open(os.path.join(directory, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("format") != ARTIFACT_FORMAT:
        raise IOError(
            f"{directory} is not a DA artifact (format="
            f"{manifest.get('format')!r}); expected {ARTIFACT_FORMAT!r}"
        )
    if manifest.get("artifact_version", 0) > ARTIFACT_VERSION:
        raise IOError(
            f"artifact version {manifest['artifact_version']} is newer than "
            f"this build understands ({ARTIFACT_VERSION})"
        )
    params = ckpt.load_tree(directory)
    plan = {k: LayerPlan.from_json(p)
            for k, p in manifest.get("plan", {}).items()}
    registry = registered_backends()
    stale = sorted({p.mode for p in plan.values() if p.mode not in registry})
    if stale:
        warnings.warn(
            f"artifact {directory} was planned for backends {stale} that are "
            "not registered in this build; those layers fall back to "
            "mode='auto' dispatch", stacklevel=2,
        )
        params = _demote_stale_modes(params, set(stale))
        plan = {k: (dataclasses.replace(p, mode="auto", source="stale")
                    if p.mode in stale else p)
                for k, p in plan.items()}
    da_cfg = DAConfig(**manifest["da_cfg"])
    model_cfg = None
    if "model_cfg" in manifest:
        from repro.models.config import ModelConfig

        raw = dict(manifest["model_cfg"])
        for key in ("mrope_sections",):  # JSON lists → tuples
            if raw.get(key) is not None:
                raw[key] = tuple(raw[key])
        model_cfg = ModelConfig(**raw)
    if "hwcost" in manifest:
        hwcost = _hwcost().HardwareCostModel.from_json(
            manifest["hwcost"])
    else:  # pre-hwcost artifact: geometry is all in the packed leaves
        hwcost = _hwcost().HardwareCostModel.from_frozen(params, plan)
    return DAArtifact(params=params, plan=plan, da_cfg=da_cfg,
                      model_cfg=model_cfg,
                      version=manifest.get("artifact_version", 1),
                      hwcost=hwcost,
                      analysis=manifest.get("analysis"))


def record_analysis(directory: str, verdict: Dict[str, Any]) -> None:
    """Stamp a ``repro.analysis.check`` verdict into an artifact's manifest.

    Read-modify-write of ``manifest.json`` under the ``"analysis"`` key,
    written atomically (tmp file + fsync + rename) so a crashed checker can
    never leave a truncated manifest.  The verdict dict is the checker's
    summary — counts per pass, error/warning totals, the ``ok`` bit and the
    checker's schema version — not the full findings list (that ships as a
    separate JSON report when asked for)."""
    path = os.path.join(directory, "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["analysis"] = verdict
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _demote_stale_modes(params: Any, stale: set) -> Any:
    def demote(leaf):
        if isinstance(leaf, PackedWeights) and leaf.mode in stale:
            return dataclasses.replace(leaf, mode="auto")
        return leaf

    return jax.tree.map(
        demote, params, is_leaf=lambda x: isinstance(x, PackedWeights)
    )


# ---------------------------------------------------------------------------
# Reporting: the Table-I trade-off, per layer
# ---------------------------------------------------------------------------


def da_memory_report(frozen_params: Any, model_cfg: Any = None,
                     kv_dtypes: Any = None) -> dict:
    """The paper's Table-I trade-off at model scale — aggregate AND per layer.

    Besides the aggregate cell counts, ``"layers"`` lists every packed matrix
    with its plan decision (mode chosen, group size) and its storage split
    (int8 code bytes vs int32 LUT bytes), so the 2^L/L blow-up is
    inspectable layer by layer, not just in aggregate.  Each layer row also
    carries its :mod:`repro.obs.hwcost` price (``da_pj`` / ``da_ns`` per
    token-pass, plus the bit-slicing counterfactual), and ``"hw"`` holds the
    model-total :meth:`HardwareCostModel.summary` — the same table serving
    ``metrics()["hw"]``, ONE source of geometry truth.

    Pass ``model_cfg`` (all-attention archs) to additionally get a ``"kv"``
    section pricing the OTHER resident tensor beside the DA weights — the
    paged KV cache: per-position page dtype, bytes per token per layer
    (codes + in-page scales), model-total bytes per token, and the capacity
    multiplier vs compute-dtype pages at equal pool bytes.
    """
    weights = luts = mats = 0
    layers = []
    hwm = _hwcost().HardwareCostModel.from_frozen(frozen_params)
    hw_rows = {r["path"]: r for r in hwm.layer_table()}
    flat, _ = jax.tree_util.tree_flatten_with_path(
        frozen_params, is_leaf=lambda x: isinstance(x, PackedWeights)
    )
    for path, leaf in flat:
        if not isinstance(leaf, PackedWeights):
            continue
        mats += 1
        weights += leaf.wq.size
        lut_sz = leaf.luts.size if leaf.luts is not None else 0
        luts += lut_sz
        hw_row = hw_rows.get(_path_key(path), {})
        layers.append({
            "layer": _path_key(path),
            "mode": leaf.mode,
            "group_size": leaf.cfg.group_size,
            "k": int(leaf.k),
            "n": int(leaf.n),
            "with_luts": leaf.has_luts,
            "code_bytes": int(leaf.wq.size) * leaf.wq.dtype.itemsize,
            "scale_bytes": int(leaf.w_scale.size) * leaf.w_scale.dtype.itemsize,
            "lut_bytes": int(lut_sz) * (leaf.luts.dtype.itemsize
                                        if leaf.luts is not None else 0),
            "cell_blowup": (lut_sz / leaf.wq.size) if leaf.wq.size else 0.0,
            "vmms_per_token": hw_row.get("vmms_per_token", 1),
            "da_pj": hw_row.get("da_pj", 0.0),
            "da_ns": hw_row.get("da_ns", 0.0),
            "bs_pj": hw_row.get("bs_pj", 0.0),
            "bs_ns": hw_row.get("bs_ns", 0.0),
        })
    report = {
        "da_matrices": mats,
        "weight_cells": weights,
        "lut_cells": luts,
        "cell_blowup": (luts / weights) if weights else 0.0,
        "layers": layers,
        "hw": hwm.summary() if hwm else None,
    }
    if model_cfg is not None and all(
            model_cfg.mixer_kind(p) == "attn"
            for p in range(model_cfg.period)):
        from repro.serve.kvcache import kv_token_bytes, resolve_kv_dtypes

        resolved = resolve_kv_dtypes(model_cfg, kv_dtypes)
        per_pos = {key: kv_token_bytes(model_cfg, dt)
                   for key, dt in resolved.items()}
        total = model_cfg.n_periods * sum(per_pos.values())
        fp_total = model_cfg.n_periods * sum(
            kv_token_bytes(model_cfg, "fp16") for _ in per_pos)
        report["kv"] = {
            "kv_dtypes": resolved,
            "token_bytes_per_layer": per_pos,
            "bytes_per_token": total,
            "fp_bytes_per_token": fp_total,
            "capacity_multiplier": fp_total / total if total else 0.0,
        }
    return report

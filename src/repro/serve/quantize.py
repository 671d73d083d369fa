"""Compat shim: model-level DA freezing moved to :mod:`repro.core.freeze`.

The old surface — ``freeze_model_da(params, cfg, mode=...)`` threading one
execution mode through every layer — is preserved for existing call sites,
but it now delegates to the artifact pipeline's planner: under
``mode="auto"`` each layer gets its own (backend, lut-or-not) plan from the
engine's one backend rule.  New code should use :func:`repro.core.freeze.freeze_model`
directly — it returns the full :class:`~repro.core.freeze.DAArtifact`
(plan included) which :func:`repro.core.freeze.save_artifact` persists for
serve-from-disk boots.

Importing this module emits a :class:`DeprecationWarning`; every in-repo
call site now imports from :mod:`repro.core.freeze`.
"""
from __future__ import annotations

import warnings

warnings.warn(
    "repro.serve.quantize is a compat shim; import from repro.core.freeze "
    "instead (the shim will be removed once external callers migrate)",
    DeprecationWarning,
    stacklevel=2,
)

from repro.core.freeze import (  # noqa: E402,F401
    DA_LEAF_NAMES,
    SKIP_CONTEXT,
    DAArtifact,
    LayerPlan,
    da_memory_report,
    freeze_model,
    freeze_model_da,
    load_artifact,
    plan_model,
    save_artifact,
)

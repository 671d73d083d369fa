"""Draft providers: the three cheap passes behind one protocol.

A provider owns the *draft side* of speculative decoding: which parameters
the draft step consumes, whether it shares the target's paged KV pools or
needs its own, roughly what a draft step costs relative to a full step
(the breakeven input), and the step function itself.  The scheduler stays
provider-agnostic — it batches draft rounds into the same pow2-bucketed
step shapes it already compiles and hands every provider the same operands.

Step contract (all providers)::

    step(params, caches, tokens [B,T], positions [B,T], page_table [B,W],
         last_idx [B]) -> (logits [B,V], caches)

``T > 1`` is the catch-up form (a provider with its own KV ingests the
tokens the target accepted since its last draft; self-draft providers share
the target pools and never need it — the target's verified KV is *better*
draft context than their own writes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax

from repro.core import engine
from repro.core.engine import PackedWeights
from repro.models.config import ModelConfig
from repro.models.model import count_params, forward
from repro.spec.decode import SpecConfig


@runtime_checkable
class DraftProvider(Protocol):
    """What the scheduler needs from a draft pass.

    name:         provider kind (metrics / logs).
    cost_ratio:   draft step cost / full step cost — the breakeven input.
    shared_cache: True → the draft writes into the TARGET's paged pools
                  (self-draft; verify overwrites its rows at full precision)
                  and never needs catch-up; False → the provider carries its
                  own pools, indexed by the same page tables.
    cfg:          ModelConfig the draft step runs under (positions /
                  M-RoPE shaping).
    params:       pytree the step consumes (jit argument, never baked in).
    """

    name: str
    cost_ratio: float
    shared_cache: bool
    cfg: ModelConfig
    params: Any

    def make_step(self) -> Callable:
        """Build the (untraced) draft step function; the scheduler jits it."""
        ...

    def init_caches(self, n_pages: int, page_size: int) -> Optional[Any]:
        """Provider-owned paged pools (None when ``shared_cache``)."""
        ...


def _artifact_x_bits(params: Any) -> Optional[int]:
    """x_bits of the first PackedWeights leaf, or None for float trees."""
    for leaf in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, PackedWeights)
    ):
        if isinstance(leaf, PackedWeights):
            return leaf.cfg.x_bits
    return None


class TruncatedBitplaneDraft:
    """Self-draft by bit-plane truncation (the DA-native drafter).

    Every DA linear of the *same* frozen artifact evaluates only the top
    ``x_bits_eff`` of its ``x_bits`` input bit-planes
    (:func:`repro.core.da.truncate_codes`): fewer bit-serial cycles against
    the same stored weight-sums, zero extra weight memory, works on
    artifact-frozen models straight off disk.  Draft cost scales with the
    evaluated planes, so ``cost_ratio = x_bits_eff / x_bits``.
    """

    name = "bitplane"
    shared_cache = True

    def __init__(self, cfg: ModelConfig, params: Any, x_bits_eff: int = 4):
        full = _artifact_x_bits(params)
        if full is None:
            raise ValueError(
                "truncated-bitplane self-draft needs DA-frozen params "
                "(PackedWeights leaves) — float weights have no bit-planes "
                "to truncate; freeze the model or pick another provider"
            )
        if not 1 <= x_bits_eff <= full:
            raise ValueError(
                f"draft_x_bits={x_bits_eff} outside [1, artifact x_bits={full}]"
            )
        self.cfg = cfg
        self.params = params
        self.x_bits_eff = x_bits_eff
        self.cost_ratio = x_bits_eff / full

    def make_step(self):
        cfg, bits = self.cfg, self.x_bits_eff

        def step(params, caches, tokens, positions, page_table, last_idx):
            # trace-time override: the whole forward quantizes as usual but
            # every engine backend walks only the top `bits` planes
            with engine.x_bits_override(bits):
                logits, caches = forward(
                    params, tokens, cfg, positions=positions, caches=caches,
                    update_cache=True, page_table=page_table,
                    last_idx=last_idx,
                )
            return logits[:, 0], caches

        return step

    def init_caches(self, n_pages: int, page_size: int) -> None:
        return None


class LayerSkipDraft:
    """Early-exit self-draft: run the first ``draft_periods`` period groups
    of the same weights, then the final norm + LM head (selfspec-style).

    The draft writes KV only for the layers it runs; verify overwrites every
    layer of the window at full precision, and the layers the draft reads
    hold the target's verified KV for all past positions — reusing the
    target cache is exactly what makes self-drafting cheap.
    """

    name = "layerskip"
    shared_cache = True

    def __init__(self, cfg: ModelConfig, params: Any,
                 draft_periods: Optional[int] = None):
        n = cfg.n_periods
        dp = draft_periods if draft_periods is not None else max(1, n // 2)
        if not 1 <= dp <= n:
            raise ValueError(
                f"draft_periods={dp} outside [1, n_periods={n}]"
            )
        self.cfg = cfg
        self.params = params
        self.draft_periods = dp
        self.cost_ratio = dp / n

    def make_step(self):
        cfg, dp = self.cfg, self.draft_periods
        dcfg = dataclasses.replace(cfg, n_layers=dp * cfg.period)

        def step(params, caches, tokens, positions, page_table, last_idx):
            # the paged pools stay whole: the draft's layer scan indexes
            # layers 0..dp-1 of them in place and leaves the rest untouched
            head_params = {
                **params,
                "periods": jax.tree.map(lambda a: a[:dp], params["periods"]),
            }
            logits, caches = forward(
                head_params, tokens, dcfg, positions=positions,
                caches=caches, update_cache=True,
                page_table=page_table, last_idx=last_idx,
            )
            return logits[:, 0], caches

        return step

    def init_caches(self, n_pages: int, page_size: int) -> None:
        return None


class ArtifactDraft:
    """A second frozen DAArtifact as the drafter (classic two-model spec).

    The draft model shares the tokenizer/vocabulary but carries its own
    paged pools — sized and page-table-indexed identically to the target's,
    so one host-side page table drives both (the lane's physical page ids
    are valid in either pool).  Catch-up: the provider has written KV up to
    the scheduler-tracked ``draft_pos``; the first draft step of a round
    feeds everything the target accepted since.
    """

    name = "artifact"
    shared_cache = False

    def __init__(self, target_cfg: ModelConfig, draft_cfg: ModelConfig,
                 draft_params: Any):
        if draft_cfg.vocab != target_cfg.vocab:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab} != target vocab "
                f"{target_cfg.vocab} — spec decoding needs one token space"
            )
        for pos in range(draft_cfg.period):
            if draft_cfg.mixer_kind(pos) != "attn":
                raise ValueError(
                    "artifact draft models must be attention stacks (their "
                    "KV rides the same page tables as the target's)"
                )
        self.cfg = draft_cfg
        self.params = draft_params
        self.cost_ratio = min(
            1.0, count_params(draft_cfg) / max(1, count_params(target_cfg))
        )

    def make_step(self):
        cfg = self.cfg

        def step(params, caches, tokens, positions, page_table, last_idx):
            logits, caches = forward(
                params, tokens, cfg, positions=positions, caches=caches,
                update_cache=True, page_table=page_table, last_idx=last_idx,
            )
            return logits[:, 0], caches

        return step

    def init_caches(self, n_pages: int, page_size: int):
        from repro.serve.kvcache import init_paged_caches

        return init_paged_caches(self.cfg, n_pages, page_size,
                                 self.cfg.dtype())


def make_provider(spec: SpecConfig, cfg: ModelConfig,
                  params: Any) -> DraftProvider:
    """Resolve a SpecConfig to a constructed provider for ``(cfg, params)``."""
    if spec.provider == "bitplane":
        return TruncatedBitplaneDraft(cfg, params,
                                      x_bits_eff=spec.draft_x_bits)
    if spec.provider == "layerskip":
        return LayerSkipDraft(cfg, params, draft_periods=spec.draft_periods)
    if spec.provider == "artifact":
        if spec.draft_params is not None:
            if spec.draft_model_cfg is None:
                raise ValueError(
                    "draft_params without draft_model_cfg — pass both"
                )
            return ArtifactDraft(cfg, spec.draft_model_cfg, spec.draft_params)
        if spec.draft_artifact is None:
            raise ValueError(
                "provider='artifact' needs draft_artifact=DIR (a frozen "
                "DAArtifact directory) or in-memory draft_params + "
                "draft_model_cfg"
            )
        from repro.core.freeze import load_artifact

        art = load_artifact(spec.draft_artifact)
        if art.model_cfg is None:
            raise ValueError(
                f"draft artifact {spec.draft_artifact} carries no model "
                "config; freeze it with freeze_model(..., model_cfg=cfg)"
            )
        return ArtifactDraft(cfg, art.model_cfg, art.params)
    raise ValueError(
        f"unknown draft provider {spec.provider!r} "
        "(expected bitplane | layerskip | artifact)"
    )

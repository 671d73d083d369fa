"""Serving engine: continuous batching with KV caches and DA-quantized
weights (the paper's inference setting — weights constant, the DA
precondition).

``ServeEngine`` is a thin facade over two runtimes:

* ``runtime="paged"`` (default for attention stacks) — the continuous-
  batching scheduler in ``repro.serve.scheduler``: paged KV cache, admission
  queue with token-budget policy, chunked prefill coalesced into the decode
  batch, preemption, streaming callbacks and latency metrics.
* ``runtime="slots"`` — the legacy fixed-slot runtime kept for architectures
  whose mixers hold O(1) state (Mamba/hybrid stacks gain nothing from KV
  paging) and as the benchmark baseline. Its per-slot prefill pads prompts
  to power-of-two length buckets (O(log max_len) compilations instead of one
  per prompt length) and scatters the fresh KV into the batch tree inside
  the same jitted call.

DA quantization is wired through the artifact pipeline (repro.core.freeze):
pass ``da_mode`` — ``"auto"`` plans a backend/LUT decision per layer by the
engine's one backend rule; a registered backend name pins every layer — and
float params are frozen into PackedWeights artifacts whose every
linear runs the multiplier-free datapath.  ``ServeEngine.from_artifact``
boots the same engine from a persisted artifact directory with zero float
weights and zero re-packing; ``save_artifact`` writes one.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.model import forward, init_caches
from repro.obs import Observability, write_chrome_trace, write_prometheus
from repro.obs.trace import request_track
from repro.serve.scheduler import (  # noqa: F401  (Request re-exported)
    PagedScheduler,
    Request,
    base_metrics,
    latency_metrics,
    mk_positions,
    pow2_bucket,
    sample_keys,
)


def make_prefill_step(cfg: ModelConfig):
    """(params, caches, tokens [B,T], positions) → (logits_last [B,V], caches)."""

    def prefill(params, caches, tokens, positions):
        logits, caches = forward(
            params, tokens, cfg, positions=positions, caches=caches,
            update_cache=True, last_logit_only=cfg.prefill_last_only,
        )
        return logits[:, -1], caches

    return prefill


def make_serve_step(cfg: ModelConfig):
    """Single-token decode: (params, caches, token [B,1], pos [B,1]) →
    (logits [B,V], caches). This is the dry-run's decode workload."""

    def serve_step(params, caches, token, positions):
        logits, caches = forward(
            params, token, cfg, positions=positions, caches=caches
        )
        return logits[:, 0], caches

    return serve_step


def scatter_cache_row(caches, c1, slot):
    """Copy batch row 0 of the batch-1 cache tree ``c1`` into row ``slot``
    (python int or traced scalar) of the batch tree. Cache layouts: KVCache
    k/v [P, B, S, kv, hd]; MambaCache conv [P, B, C-1, ch], ssm [P, B, H,
    Pd, S]; stacked scalar KVCache.length [P] takes the elementwise max
    (per-slot lengths are tracked host-side and masked via positions)."""

    def one(big, small):
        if big.ndim == 1:
            return jnp.maximum(big, small)
        return jax.lax.dynamic_update_slice(
            big, small.astype(big.dtype), (0, slot) + (0,) * (big.ndim - 2)
        )

    return jax.tree.map(one, caches, c1)


def make_prefill_into_slot(cfg: ModelConfig, max_len: int):
    """Slot prefill, one compilation per length bucket: (params, caches,
    tokens [1,T_bucket], positions, last_idx [1], slot) → (logits [1,V],
    caches). The batch-1 prefill caches are zeros created inside the trace
    and the fresh KV is scattered into row ``slot`` of the batch tree with
    one dynamic_update_slice per leaf — no host-side batch-1 cache init, no
    O(tree) host round-trip, and ``slot`` is a traced operand so every slot
    shares the compilation."""

    def prefill(params, caches, tokens, positions, last_idx, slot):
        c1 = init_caches(cfg, 1, max_len, cfg.dtype())
        logits, c1 = forward(params, tokens, cfg, positions=positions,
                             caches=c1, update_cache=True, last_idx=last_idx)
        return logits[:, 0], scatter_cache_row(caches, c1, slot)

    return prefill


class _SlotRuntime:
    """Fixed-slot continuous batching over a dense [B, max_len] cache."""

    def __init__(self, cfg: ModelConfig, params: Any, batch_size: int,
                 max_len: int, greedy: bool = True,
                 obs: Optional[Observability] = None):
        self.cfg = cfg
        self.params = params
        self.b = batch_size
        self.max_len = max_len
        self.greedy = greedy
        self.caches = init_caches(cfg, batch_size, max_len, cfg.dtype())
        # prompt padding is only sound for attention mixers (pad KV rows stay
        # masked until decode overwrites them); the Mamba/SSD recurrence has
        # no position mask, so pad tokens would corrupt the carried conv/ssm
        # state — those archs prefill at exact prompt length
        self._bucketed = all(cfg.mixer_kind(p) == "attn"
                             for p in range(cfg.period))
        # same registry homing as the paged scheduler (prefill_compiles
        # survives as a property — tests read it as an attribute)
        self.obs = obs if obs is not None else Observability.make()
        reg = self.obs.registry
        self._tr = self.obs.tracer
        self._c_prefill_compiles = reg.counter(
            "slot_prefill_compiles", "per-slot prefill shape compiles")
        self._c_out = reg.counter("sched_out_tokens", "tokens emitted")
        self._h_ttft = reg.histogram(
            "req_ttft_seconds", "submit to first token")
        self._h_itl = reg.histogram(
            "req_itl_seconds", "inter-token latency")
        base = make_prefill_into_slot(cfg, max_len)

        def counted(*a):
            # trace-time side effect = 1 / bucket
            self._c_prefill_compiles.inc()
            return base(*a)

        self._prefill_into = jax.jit(counted)
        self._decode = jax.jit(make_serve_step(cfg))
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.slot_len = np.zeros(batch_size, dtype=np.int64)
        self.cur_token = np.zeros(batch_size, dtype=np.int32)
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}

    @property
    def prefill_compiles(self) -> int:
        return int(self._c_prefill_compiles.total)

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt of {len(req.prompt)} tokens does "
                f"not fit max_len={self.max_len}"
            )
        req.submit_t = time.perf_counter()
        self.queue.append(req)
        if self._tr.enabled:
            self._tr.instant("submit", request_track(req.uid),
                             ts=req.submit_t, prompt_tokens=len(req.prompt),
                             max_new_tokens=req.max_new_tokens)

    def _admit(self) -> None:
        for i in range(self.b):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                self._prefill_slot(i, req)

    def _prefill_slot(self, i: int, req: Request) -> None:
        """Bucketed per-slot prefill straight into slot ``i``.

        For attention stacks the prompt is padded to the next power-of-two
        length (capped at max_len); pad tokens write cache rows past the
        real length, which stay masked (`kpos <= tpos`) until decode
        overwrites them — so 10 distinct prompt lengths cost O(log)
        compilations, not 10. Mamba/hybrid stacks use the exact length."""
        cfg = self.cfg
        t0 = len(req.prompt)
        if self._tr.enabled:
            self._tr.begin("running", request_track(req.uid), slot=i,
                           prompt_tokens=t0)
        t_pf = time.perf_counter()
        bucket = min(pow2_bucket(t0, lo=4), self.max_len) if self._bucketed \
            else t0
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :t0] = req.prompt
        pos = mk_positions(cfg, jnp.arange(bucket, dtype=jnp.int32)[None])
        logits, self.caches = self._prefill_into(
            self.params, self.caches, jnp.asarray(toks), pos,
            jnp.asarray([t0 - 1], dtype=jnp.int32),
            jnp.asarray(i, dtype=jnp.int32),
        )
        tok = int(jnp.argmax(logits[0])) if self.greedy else int(
            jax.random.categorical(jax.random.key(req.uid), logits[0])
        )
        now = time.perf_counter()
        req.first_token_t = now
        self._h_ttft.observe(now - req.submit_t)
        req.token_times.append(now)
        req.generated.append(tok)
        self._c_out.inc()
        if self._tr.enabled:
            track = request_track(req.uid)
            self._tr.complete("prefill", track, t_pf, now - t_pf,
                              tokens=t0, bucket=bucket)
            self._tr.instant("token", track, ts=now, n=1)
        if req.on_token is not None:
            req.on_token(req.uid, tok)
        self.slots[i] = req
        self.slot_len[i] = t0 + 1
        self.cur_token[i] = tok

    # -- decode --------------------------------------------------------------
    def step(self) -> int:
        """One batched decode step over all active slots; returns #active."""
        self._admit()
        active = [i for i in range(self.b) if self.slots[i] is not None]
        if not active:
            return 0
        toks = jnp.asarray(self.cur_token, dtype=jnp.int32)[:, None]
        pos = mk_positions(
            self.cfg, jnp.asarray(self.slot_len - 1, dtype=jnp.int32)[:, None]
        )
        logits, self.caches = self._decode(self.params, self.caches, toks, pos)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), dtype=np.int32)
        now = time.perf_counter()
        for i in active:
            req = self.slots[i]
            if self.greedy:
                tok = int(nxt[i])
            else:
                key = jax.random.wrap_key_data(jnp.asarray(sample_keys(
                    (req.uid << 20) + len(req.generated))[0]))
                tok = int(jax.random.categorical(key, logits[i]))
            if req.token_times:
                self._h_itl.observe(now - req.token_times[-1])
            req.token_times.append(now)
            req.generated.append(tok)
            self._c_out.inc()
            if self._tr.enabled:
                self._tr.instant("token", request_track(req.uid), ts=now,
                                 n=len(req.generated))
            if req.on_token is not None:
                req.on_token(req.uid, tok)
            self.slot_len[i] += 1
            self.cur_token[i] = tok
            exhausted = len(req.generated) >= req.max_new_tokens
            if tok == req.eos_id or exhausted or self.slot_len[i] >= self.max_len:
                req.finish_t = now
                self.done[req.uid] = req
                self.slots[i] = None
                if self._tr.enabled:
                    track = request_track(req.uid)
                    self._tr.instant("finish", track, ts=now,
                                     tokens=len(req.generated))
                    self._tr.end("running", track, ts=now)
        return len(active)

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        return self.done

    def warmup(self) -> int:
        """Pre-compile the prefill length buckets + the decode step; outputs
        are discarded, engine caches are left untouched. Non-bucketed archs
        (Mamba/hybrid prefill at exact prompt length) warm the decode step
        only — their prefill shapes are not knowable in advance."""
        buckets, b = [], 4
        while self._bucketed and b < self.max_len:
            buckets.append(b)
            b *= 2
        if self._bucketed:
            buckets.append(self.max_len)
        for t in dict.fromkeys(buckets):
            toks = jnp.zeros((1, t), jnp.int32)
            pos = mk_positions(self.cfg, jnp.arange(t, dtype=jnp.int32)[None])
            self._prefill_into(self.params, self.caches, toks, pos,
                               jnp.asarray([t - 1], dtype=jnp.int32),
                               jnp.asarray(0, dtype=jnp.int32))
        self._decode(self.params, self.caches,
                     jnp.zeros((self.b, 1), jnp.int32),
                     mk_positions(self.cfg, jnp.zeros((self.b, 1), jnp.int32)))
        return len(buckets) + 1

    def metrics(self) -> Dict[str, Any]:
        return {
            **base_metrics("slots", self.done, int(self._c_out.total)),
            "prefill_compiles": self.prefill_compiles,
        }


class ServeEngine:
    """Facade: freeze-once DA weights in front, one of two serving runtimes
    behind (``PagedScheduler`` or the legacy slot runtime)."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        batch_size: int,
        max_len: int,
        greedy: bool = True,
        da_mode: Optional[str] = None,
        da_pin_modes: bool = True,
        runtime: str = "auto",
        page_size: int = 16,
        n_pages: Optional[int] = None,
        prefill_chunk: int = 16,
        prefill_lanes: Optional[int] = None,
        token_budget: Optional[int] = None,
        admission: str = "reserve",
        spec=None,
        prefix_cache: bool = False,
        paged_attn: Optional[str] = None,
        kv_dtype: Optional[str] = None,
        kv_dtypes: Optional[Dict[str, str]] = None,
        trace: bool = False,
        obs: Optional[Observability] = None,
        hw=None,
        analysis_debug: bool = False,
    ):
        # paged_attn: the paged-attention read backend — "gather" (XLA
        # page-table gather), "fused" (Pallas in-kernel page walk; interpret
        # mode off-TPU) or "auto" (fused on TPU, gather elsewhere).  None
        # inherits cfg.paged_attn.  The two reads agree to a few ulps (the
        # fused one sums each row's keys block by block).
        # kv_dtype: KV page precision — "fp16" (compute-dtype pages, today's
        # layout), "int8" or "int4" (quantized codes with in-page dequant
        # scales).  None inherits cfg.kv_dtype.  kv_dtypes overrides per
        # layer position ({"pos_i": dtype}, missing positions follow
        # kv_dtype) — the freeze planner's per-layer escape hatch.
        # spec: speculative decoding over the paged runtime — a
        # repro.spec.SpecConfig, or a provider-name shorthand
        # ("bitplane" | "layerskip" | "artifact" → defaults).  Drafts gamma
        # tokens with the provider's cheap pass, verifies them in one
        # batched full-precision step; greedy output is token-identical to
        # non-speculative decoding.
        # prefix_cache: shared-prefix caching over the paged KV pool —
        # requests sharing a prompt prefix (system prompts, few-shot
        # headers) reuse its KV pages instead of re-prefilling them;
        # refcounted pages with copy-on-write keep decoded tokens
        # bit-identical to caching off.
        # da_mode: freeze float params through the DA artifact pipeline
        # ("auto" plans a backend per layer by the engine's backend rule at
        # m = batch_size; a registered backend name pins every layer).
        # Params that already carry PackedWeights leaves (a loaded artifact)
        # are never re-packed.  da_pin_modes=False keeps runtime shape
        # dispatch on the frozen artifact (prefill and decode may pick
        # different backends) instead of baking in the decode-batch plan.
        # trace: turn on the structured event recorder (request lifecycle +
        # scheduler tick spans; export with write_trace()).  The metrics
        # registry is always on — tracing is the opt-in half.  obs= hands in
        # a pre-built Observability bundle instead (overrides trace=); each
        # engine otherwise builds its own, so two engines in one process
        # never share series.
        # hw: a repro.obs.hwcost.HardwareCostModel pricing the serving work
        # on the paper's DA circuits.  None derives it from the artifact
        # being frozen/loaded (or the already-frozen params); float-weight
        # engines have no DA geometry, so attribution stays off.
        # Bake the KV precision into cfg BEFORE freezing, so the artifact's
        # model config and plan record the precision this engine serves at
        # (from_artifact then rebuilds a matching pool without being told).
        if kv_dtype is not None and kv_dtype != cfg.kv_dtype:
            cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        self.artifact = None
        if (da_mode is not None and da_mode != "float"
                and not _is_frozen(params)):
            from repro.core.da import DAConfig
            from repro.core.freeze import freeze_model

            self.artifact = freeze_model(
                params, DAConfig(x_signed=True), mode=da_mode,
                m_hint=batch_size, model_cfg=cfg, pin_modes=da_pin_modes,
                kv_dtype_overrides=kv_dtypes,
            )
            params = self.artifact.params
        if hw is None:
            if self.artifact is not None:
                hw = self.artifact.hwcost
            elif _is_frozen(params):
                from repro.obs.hwcost import HardwareCostModel

                hw = HardwareCostModel.from_frozen(params)
        self.hw = hw if hw else None
        # the engine always uses the sliced prefill head (strictly better)
        cfg = dataclasses.replace(cfg, prefill_last_only=True)
        self.cfg = cfg
        self.params = params
        self.b = batch_size
        self.max_len = max_len
        if runtime == "auto":
            all_attn = all(cfg.mixer_kind(p) == "attn"
                           for p in range(cfg.period))
            runtime = "paged" if all_attn else "slots"
        self.runtime = runtime
        self.obs = obs if obs is not None else Observability.make(trace=trace)
        if isinstance(spec, str):
            from repro.spec import SpecConfig

            spec = SpecConfig(provider=spec)
        if runtime == "paged":
            self._rt = PagedScheduler(
                cfg, params, batch_size=batch_size, max_len=max_len,
                greedy=greedy, page_size=page_size, n_pages=n_pages,
                prefill_chunk=prefill_chunk, prefill_lanes=prefill_lanes,
                token_budget=token_budget, admission=admission, spec=spec,
                prefix_cache=prefix_cache, paged_attn=paged_attn,
                kv_dtypes=kv_dtypes, obs=self.obs, hw=self.hw,
                analysis_debug=analysis_debug,
            )
        elif runtime == "slots":
            quantized = cfg.kv_dtype != "fp16" or any(
                dt != "fp16" for dt in (kv_dtypes or {}).values())
            if quantized:
                raise ValueError(
                    "quantized KV (kv_dtype/kv_dtypes) lives in the paged "
                    "runtime's page pool; the dense slot runtime has no "
                    "pages — drop kv_dtype= or use runtime='paged'"
                )
            if paged_attn not in (None, "auto"):
                raise ValueError(
                    "paged_attn selects the paged runtime's attention read; "
                    "the dense slot runtime has no page tables — drop "
                    "paged_attn= or use runtime='paged'"
                )
            if spec is not None:
                raise ValueError(
                    "speculative decoding runs on the paged runtime only "
                    "(draft rollback needs page tables); drop spec= or use "
                    "runtime='paged'"
                )
            if prefix_cache:
                raise ValueError(
                    "prefix caching shares physical KV pages between "
                    "requests; the dense slot runtime has no page tables to "
                    "share — drop prefix_cache= or use runtime='paged'"
                )
            if analysis_debug:
                raise ValueError(
                    "analysis_debug validates paged-pool launch plans; the "
                    "dense slot runtime has no pages — drop analysis_debug= "
                    "or use runtime='paged'"
                )
            self._rt = _SlotRuntime(cfg, params, batch_size, max_len, greedy,
                                    obs=self.obs)
        else:
            raise ValueError(f"unknown runtime {runtime!r} "
                             "(expected auto | paged | slots)")

    # -- freeze-once, serve-many ---------------------------------------------
    @classmethod
    def from_artifact(
        cls,
        directory: str,
        batch_size: int,
        max_len: int,
        greedy: bool = True,
        **runtime_kw,
    ) -> "ServeEngine":
        """Boot the full serving runtime from a persisted DA artifact: the
        packed weights come straight off disk — no float params, no
        re-packing (the paper's freeze-once premise, operationally).

        KV precision follows the artifact: the plan's wk entries record the
        per-position page dtype the model was frozen for, and the pool is
        built to match — an artifact frozen at int8 cannot silently boot an
        fp16 pool.  An explicit ``kv_dtype=`` in ``runtime_kw`` overrides a
        HOMOGENEOUS plan (re-serving an old fp16 artifact quantized, or
        vice versa — decode is cache-precision-, not weight-, dependent);
        overriding a plan with per-layer escape hatches would silently
        flatten them, so that raises instead."""
        from repro.core.freeze import load_artifact

        art = load_artifact(directory)
        if art.model_cfg is None:
            raise ValueError(
                f"artifact {directory} carries no model config; freeze with "
                "freeze_model(..., model_cfg=cfg) to make it servable"
            )
        plan_kv: Dict[str, str] = {}
        for key, p in art.plan.items():
            if p.kv_dtype is not None and key.endswith("/wk"):
                seg = next((s for s in key.split("/")
                            if s.startswith("pos_")), None)
                if seg is not None:
                    plan_kv[seg] = p.kv_dtype
        explicit = (runtime_kw.get("kv_dtype") is not None
                    or bool(runtime_kw.get("kv_dtypes")))
        if explicit and len(set(plan_kv.values())) > 1:
            raise ValueError(
                f"artifact {directory} was frozen with per-layer KV dtypes "
                f"{plan_kv}; overriding them with a global kv_dtype= would "
                "silently flatten the plan — drop the override or re-freeze"
            )
        if not explicit and plan_kv:
            runtime_kw = dict(runtime_kw, kv_dtypes=plan_kv)
        runtime_kw.setdefault("hw", art.hwcost)  # the manifest's cost table
        eng = cls(art.model_cfg, art.params, batch_size, max_len,
                  greedy=greedy, **runtime_kw)
        eng.artifact = art
        return eng

    def save_artifact(self, directory: str) -> str:
        """Persist this engine's frozen weights + plan for later cold boots."""
        from repro.core.freeze import save_artifact

        if self.artifact is None:
            raise ValueError(
                "engine holds no DAArtifact (constructed without da_mode and "
                "not from_artifact) — nothing to save"
            )
        return save_artifact(directory, self.artifact)

    # -- runtime delegation --------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        return self._rt.queue

    @property
    def done(self) -> Dict[int, Request]:
        return self._rt.done

    @property
    def caches(self):
        return self._rt.caches

    def submit(self, req: Request) -> None:
        self._rt.submit(req)

    def step(self) -> int:
        return self._rt.step()

    def run(self, max_steps: int = 100_000) -> Dict[int, Request]:
        return self._rt.run(max_steps)

    def warmup(self) -> int:
        """Pre-compile every step-shape bucket of the active runtime."""
        return self._rt.warmup()

    def metrics(self) -> Dict[str, Any]:
        return self._rt.metrics()

    # -- observability export ------------------------------------------------
    def metrics_snapshot(self) -> Dict[str, Any]:
        """Flat registry snapshot (every counter/gauge/histogram series) —
        the schema BENCH_*.json and the Prometheus exporter share."""
        return self.obs.registry.snapshot()

    def write_trace(self, path: str) -> str:
        """Dump the recorded events as Chrome trace_event JSON (load the
        file in Perfetto / chrome://tracing).  Requires trace=True (or an
        enabled recorder via obs=) — an empty trace is written otherwise."""
        return write_chrome_trace(path, self.obs.tracer)

    def write_metrics(self, path: str) -> str:
        """Dump the registry in Prometheus text exposition format."""
        return write_prometheus(path, self.obs.registry)

    def write_hw_metrics(self, path: str) -> str:
        """Dump ``metrics()["hw"]`` — the DA hardware-cost block — as
        schema-stamped JSON (what ``repro.obs.check`` validates and the
        ``--hw-metrics`` launcher knob writes).  ``hw`` is null when the
        engine has no cost model (float weights)."""
        import json

        from repro.obs.metrics import METRICS_SCHEMA_VERSION

        payload = {"metrics_schema_version": METRICS_SCHEMA_VERSION,
                   "hw": self.metrics().get("hw")}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        return path


def _is_frozen(params: Any) -> bool:
    """Does the tree already carry PackedWeights leaves (a DA artifact)?"""
    from repro.core.engine import PackedWeights

    return any(
        isinstance(leaf, PackedWeights)
        for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, PackedWeights)
        )
    )

"""Continuous-batching scheduler over the paged KV cache.

The paper's freeze-once/serve-many premise puts all serving cost in the
decode hot loop; this module is the periphery engineering around the
constant-weight DA arrays — the piece DAISM and the RRAM benchmarking
framework both identify as where in-memory VMM wins are made or lost.

One fixed decode batch of ``batch_size`` lanes runs every tick. Because the
page pool is batch-free (requests own pages, not batch rows), one tick can
issue TWO economically-shaped calls of the same unified jitted step instead
of one padded monolith: a compact chunked-prefill sub-batch
(``[prefill_lanes, chunk]``, lanes still ingesting their prompt) and a pure
decode batch (``[batch_size, 1]``) — chunked prefill proceeds beside the
decode batch every tick without inflating its width, and a lane that
finishes its prompt mid-tick starts decoding the same tick. Step shapes are
length-bucketed to powers of two, so prefill compiles O(log chunk) shapes,
not O(#prompt-lengths).

Host-side state (the scheduler) vs device state (the paged pools):

* admission queue with a token-budget policy — ``token_budget`` caps tokens
  processed per step (decode lanes are reserved first; prefill chunks fill
  the remainder), and ``admission="reserve"`` only admits a request when its
  worst-case page demand fits beside the reservations of every running lane
  (pure backpressure: the queue waits, nothing crashes);
* ``admission="optimistic"`` admits on first-chunk fit and relies on
  preemption — when a decoding lane cannot get a page, the youngest lane is
  evicted back to the queue head (pages freed, KV recomputed on
  re-admission, exactly reproducing its tokens under greedy decoding);
* finished lanes free their pages immediately; lanes that make no progress
  for ``stall_patience`` consecutive steps are preempted too;
* per-request streaming callbacks (``Request.on_token``) and wall-clock
  latency/throughput metrics (TTFT, inter-token p50/p99) come for free from
  the host loop;
* ``prefix_cache=True`` turns on shared-prefix caching: fully-ingested
  prompt pages are indexed in a host-side trie (``kvcache.PrefixCache``),
  admission looks the new prompt up and skips prefill for every cached page
  (the pages are shared by refcount; the hit shrinks both the chunk plan
  and the admission reservation), writes into a still-shared last page
  copy-on-write first, and pool pressure evicts LRU cached prefixes before
  backpressure kicks in.  Decoded tokens are bit-identical with the cache
  on or off — hits reuse KV a previous request computed over the exact
  same prefix.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import shard_paged_caches
from repro.models.config import ModelConfig
from repro.models.model import forward
from repro.obs import Observability
from repro.obs.hwcost import HardwareCostModel, draft_price
from repro.obs.metrics import ENERGY_BUCKETS
from repro.obs.trace import SCHED_TRACK, request_track
from repro.serve.kvcache import (
    GARBAGE_PAGE,
    PagePool,
    PrefixCache,
    checkpoint as kv_checkpoint,
    copy_page,
    defrag,
    init_paged_caches,
    kv_cache_nbytes,
    kv_page_bytes,
    kv_token_bytes,
    pad_position,
    pages_for,
    resolve_kv_dtypes,
    rollback as kv_rollback,
    table_width,
)
from repro.spec import (
    SpecConfig,
    breakeven_acceptance,
    greedy_accept,
    make_provider,
    make_verify_step,
)
from repro.spec.decode import make_fused_draft, mk_positions  # noqa: F401
# (mk_positions re-exported: serve.engine and the examples import it here)


@dataclasses.dataclass
class Request:
    """One generation request (re-exported by ``repro.serve.engine``)."""

    uid: int
    prompt: np.ndarray            # [T0] int32
    max_new_tokens: int = 32
    eos_id: int = -1              # -1 → never stops early
    on_token: Optional[Callable[[int, int], None]] = None  # stream (uid, tok)
    generated: Optional[List[int]] = None
    # wall-clock metrics, stamped by the runtime (first_token_t is None until
    # the first token lands — perf_counter can legally return exactly 0.0, so
    # "unset" must not be encoded as a float value)
    submit_t: float = 0.0
    first_token_t: Optional[float] = None
    finish_t: float = 0.0
    token_times: Optional[List[float]] = None
    # estimated DA-hardware cost of this request's executed work (pJ /
    # model-ns), accumulated by the scheduler when a HardwareCostModel is
    # attached; stays 0.0 otherwise
    hw_pj: float = 0.0
    hw_ns: float = 0.0

    def __post_init__(self):
        if self.generated is None:
            self.generated = []
        if self.token_times is None:
            self.token_times = []


def latency_metrics(reqs) -> Dict[str, float]:
    """TTFT and inter-token latency percentiles (ms) over finished requests.

    Zeroed keys (never a crash) when nothing has finished yet; a request
    whose first token landed at wall-clock 0.0 exactly still counts — the
    unset sentinel is None, not falsiness."""
    itl: List[float] = []
    for r in reqs:
        itl.extend(b - a for a, b in zip(r.token_times, r.token_times[1:]))
    ttft = [r.first_token_t - r.submit_t for r in reqs
            if r.first_token_t is not None]

    def pct(xs, q):
        return float(np.percentile(xs, q)) * 1e3 if xs else 0.0

    return {
        "ttft_p50_ms": pct(ttft, 50),
        "itl_p50_ms": pct(itl, 50),
        "itl_p99_ms": pct(itl, 99),
    }


def base_metrics(runtime: str, done: Dict[int, Request],
                 out_tokens: int) -> Dict[str, Any]:
    """The ``metrics()`` core shared by both serving runtimes (the paged
    scheduler and the legacy slot runtime): runtime tag, completion and
    token totals, and the latency percentiles.  Runtime-specific sections
    layer on top of this one dict — the two implementations must never
    drift on the common keys."""
    return {
        "runtime": runtime,
        "requests_done": len(done),
        "out_tokens": out_tokens,
        **latency_metrics(done.values()),
    }


def pow2_bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two ≥ n (and ≥ lo) — the step-length buckets."""
    b = lo
    while b < n:
        b *= 2
    return b


def width_buckets(b: int) -> List[int]:
    """Batch-width ladder {1, 2, 3, 4, 6, 8, 12, …, b}: pow2 plus the
    1.5× midpoints — a decode batch with 9 live lanes pays for 12 rows,
    not 16. Still O(log) shapes."""
    out, w = [], 1
    while w < b:
        out.append(w)
        mid = w + w // 2
        if w > 1 and mid < b:
            out.append(mid)
        w *= 2
    out.append(b)
    return out


def width_bucket(n: int, b: int) -> int:
    """Smallest ladder width ≥ n (capped at b)."""
    for w in width_buckets(b):
        if w >= n:
            return w
    return b


def make_paged_logits(cfg: ModelConfig):
    """The paged forward: (params, caches, tokens [B,T], positions,
    page_table [B,W], last_idx [B]) → (logits [B,V], caches). T=1 is pure
    decode; T>1 coalesces prefill chunks with decoding lanes (their single
    real token rides in column 0, pad columns write to the garbage page)."""

    def step(params, caches, tokens, positions, page_table, last_idx):
        logits, caches = forward(
            params, tokens, cfg, positions=positions, caches=caches,
            update_cache=True, page_table=page_table, last_idx=last_idx,
        )
        return logits[:, 0], caches

    return step


def sample_keys(seeds) -> np.ndarray:
    """The uint32 [n, 2] words of the threefry keys of 64-bit ``seeds``
    (high word, low word): what the sampling step wraps back into keys."""
    s = np.asarray(seeds, np.uint64).reshape(-1)
    return np.stack([s >> np.uint64(32), s & np.uint64(0xFFFFFFFF)],
                    axis=-1).astype(np.uint32)


def make_paged_step(cfg: ModelConfig):
    """The unified serve step: the paged forward (``make_paged_logits``)
    ending in the greedy sampler, so a call returns the first argmax of
    each row's logits as one int32 token id per row ([B]) and the caches;
    the logits never leave the device."""
    logits_step = make_paged_logits(cfg)

    def step(params, caches, tokens, positions, page_table, last_idx):
        logits, caches = logits_step(params, caches, tokens, positions,
                                     page_table, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    return step


def make_paged_sampling_step(cfg: ModelConfig):
    """``make_paged_step`` drawing ``jax.random.categorical`` from each
    row's logits instead of taking the argmax, with a trailing ``keys``
    [B, 2] uint32 argument (``sample_keys``): one key a row."""
    logits_step = make_paged_logits(cfg)

    def step(params, caches, tokens, positions, page_table, last_idx, keys):
        logits, caches = logits_step(params, caches, tokens, positions,
                                     page_table, last_idx)
        ids = jax.vmap(jax.random.categorical)(
            jax.random.wrap_key_data(keys), logits)
        return ids.astype(jnp.int32), caches

    return step


@dataclasses.dataclass
class _Lane:
    """Host state of one occupied batch row."""

    req: Request
    pages: List[int]              # physical pages, in logical order
    ctx: List[int]                # prompt + generated-so-far token ids
    pos: int = 0                  # ctx tokens already written to the KV pool
    admitted_t: float = 0.0
    stalled_steps: int = 0
    cached: bool = False          # prompt pages already offered to the trie
    draft_pos: int = 0            # ctx tokens the DRAFT model has ingested
    #                               (own-cache providers only; self-draft
    #                               providers read the target's verified KV)

    @property
    def remaining(self) -> int:   # 1 → decoding; >1 → still prefilling
        return len(self.ctx) - self.pos


class PagedScheduler:
    """Continuous batching + paged KV: the serving runtime behind
    ``ServeEngine(runtime="paged")``."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        batch_size: int,
        max_len: int,
        greedy: bool = True,
        page_size: int = 16,
        n_pages: Optional[int] = None,
        prefill_chunk: int = 16,
        prefill_lanes: Optional[int] = None,
        token_budget: Optional[int] = None,
        admission: str = "reserve",
        stall_patience: int = 64,
        spec: Optional[SpecConfig] = None,
        prefix_cache: bool = False,
        paged_attn: Optional[str] = None,
        kv_dtype: Optional[str] = None,
        kv_dtypes: Optional[Dict[str, str]] = None,
        obs: Optional[Observability] = None,
        hw: Optional[HardwareCostModel] = None,
        analysis_debug: bool = False,
    ):
        if admission not in ("reserve", "optimistic"):
            raise ValueError(f"unknown admission policy {admission!r}")
        if paged_attn is not None and paged_attn != cfg.paged_attn:
            # the runtime knob overrides the model config's paged-attention
            # backend; bake it in before any step/provider closure captures
            # cfg (plain decode, spec draft/verify and warmup all trace it)
            cfg = dataclasses.replace(cfg, paged_attn=paged_attn)
        if kv_dtype is not None and kv_dtype != cfg.kv_dtype:
            # same override pattern for the KV page precision: baked into cfg
            # so spec draft providers and any cfg-derived pool agree with the
            # scheduler's own pool
            cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        # resolve + validate per-position KV dtypes ONCE, loudly, before any
        # pool memory is allocated (unknown dtype / int4 with odd head_dim)
        self.kv_dtypes = resolve_kv_dtypes(cfg, kv_dtypes)
        if spec is not None and not greedy:
            raise ValueError(
                "speculative decoding verifies drafts by greedy acceptance; "
                "it requires greedy=True (sampling would need lossless "
                "rejection sampling, which this runtime does not implement)"
            )
        if n_pages is None:
            # dense-slot-equivalent footprint: every lane can hold max_len
            n_pages = batch_size * pages_for(max_len, page_size) + 1
        self.cfg = cfg
        self.params = params
        self.b = batch_size
        self.max_len = max_len
        self.greedy = greedy
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.prefill_lanes = prefill_lanes or min(4, batch_size)
        self.token_budget = token_budget or (batch_size + 2 * prefill_chunk)
        self.admission = admission
        self.stall_patience = stall_patience
        self.W = table_width(max_len, page_size)
        self.pad_pos = pad_position(max_len, page_size)
        self.pool = PagePool(
            n_pages,
            page_bytes=kv_page_bytes(cfg, page_size, self.kv_dtypes))
        self.caches = shard_paged_caches(
            init_paged_caches(cfg, n_pages, page_size, cfg.dtype(),
                              kv_dtypes=self.kv_dtypes)
        )
        self.lanes: List[Optional[_Lane]] = [None] * batch_size
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self._preempted: set = set()  # uids waiting on a full-ctx re-admit
        # shared-prefix caching: a host-side trie over page-granular prompt
        # prefixes; hits skip prefill for cached pages and share them by
        # refcount (COW guards the last partial page)
        self.prefix = PrefixCache(page_size) if prefix_cache else None
        # analysis_debug: every launch's (page, offset) write plan goes
        # through repro.analysis.races.check_plan BEFORE the device call;
        # a violated aliasing invariant raises PageRaceError instead of
        # silently corrupting KV.  plans_checked is a plain attribute, not a
        # registry counter — a debug-only metric would churn the exported
        # schema the obs regression validators pin.
        self.analysis_debug = bool(analysis_debug)
        self.plans_checked = 0
        # counters — registry-homed so metrics(), the Prometheus exporter,
        # and BENCH_*.json all read one source; the former plain attributes
        # (self.steps, self.out_tokens, ...) survive as read-only properties
        self.obs = obs if obs is not None else Observability.make()
        reg = self.obs.registry
        self._tr = self.obs.tracer
        self._c_steps = reg.counter("sched_ticks", "scheduler ticks run")
        self._c_out = reg.counter("sched_out_tokens", "tokens emitted")
        self._c_ctx = reg.counter(
            "sched_ctx_tokens", "context tokens written to the KV pool")
        self._c_preempt = reg.counter(
            "sched_preemptions", "lanes evicted back to the queue")
        self._c_compiles = reg.counter(
            "sched_step_compiles", "unified-step shape compiles")
        self._c_pref_lookups = reg.counter(
            "prefix_lookups", "admissions that consulted the prefix trie")
        self._c_pref_hits = reg.counter(
            "prefix_hits", "admissions that reused cached prefix pages")
        self._c_cow = reg.counter(
            "kv_cow_copies", "copy-on-write page copies")
        self._g_lanes = reg.gauge("sched_live_lanes", "occupied batch rows")
        self._g_queue = reg.gauge(
            "sched_queue_depth", "requests waiting for admission")
        self._g_used_pages = reg.gauge("kv_used_pages", "pool pages in use")
        self._h_ttft = reg.histogram(
            "req_ttft_seconds", "submit to first token")
        self._h_itl = reg.histogram(
            "req_itl_seconds", "inter-token latency")
        self._h_tick = reg.histogram(
            "sched_tick_seconds", "wall time of one scheduler tick")
        self._h_queue = reg.histogram(
            "req_queue_wait_seconds", "submit to admission")
        self._c_calls = reg.counter(
            "sched_device_calls", "device calls issued, by phase")
        self._c_d2h = reg.counter(
            "sched_d2h_bytes", "bytes copied device-to-host, by phase")
        self._c_attn_pages = reg.counter(
            "sched_attn_pages", "pages named by each call's real rows "
            "(kind=live) and page slots of its table, rows x width "
            "(kind=table), by phase")
        self._c_draft_steps = reg.counter(
            "spec_draft_steps", "draft-model steps issued")
        self._c_verify_steps = reg.counter(
            "spec_verify_steps", "batched verify calls issued")
        self._c_spec_rounds = reg.counter(
            "spec_rounds", "speculative rounds completed")
        self._c_drafted = reg.counter(
            "spec_drafted_tokens", "tokens proposed by the draft model")
        self._c_accepted = reg.counter(
            "spec_accepted_drafts", "draft tokens accepted by verify")
        self._c_bonus = reg.counter(
            "spec_bonus_tokens", "bonus tokens from fully-accepted windows")
        self._c_spec_off = reg.counter(
            "spec_disabled_requests", "requests whose speculation auto-off'd")
        self._c_draft_compiles = reg.counter(
            "spec_draft_compiles", "draft-step shape compiles")
        self._c_verify_compiles = reg.counter(
            "spec_verify_compiles", "verify-step shape compiles")
        self._start_t: Optional[float] = None
        base = (make_paged_step(cfg) if greedy
                else make_paged_sampling_step(cfg))

        def counted(*a):
            # trace-time side effect = 1 per bucket
            self._c_compiles.inc()
            return base(*a)

        # every step takes the KV pools (argument 1) and returns them
        # updated: donating them lets each call write into the buffers it
        # was given (callers rebind self.caches / self.draft_caches from the
        # result and never read the donated arrays again)
        self._step = jax.jit(counted, donate_argnums=(1,))

        # -- speculative decoding (draft -> batched verify) -------------------
        self.spec = spec
        self._provider = None
        self.draft_caches = None
        self._spec_state: Dict[int, Dict[str, Any]] = {}  # uid → EMA state
        if spec is not None:
            self._provider = make_provider(spec, cfg, params)
            own = self._provider.init_caches(self.pool.n_pages, page_size)
            if own is not None:
                self.draft_caches = shard_paged_caches(own)
            self._spec_floor = (
                spec.disable_below if spec.disable_below is not None
                else min(1.0, breakeven_acceptance(
                    spec.gamma, self._provider.cost_ratio) + 0.05)
            )
            # the whole gamma-token draft loop is ONE device call: catch-up
            # feed + a lax.scan of gamma-1 greedy proposals (host dispatch
            # per round, not per draft token)
            dbase = make_fused_draft(self._provider.make_step(),
                                     self._provider.cfg, spec.gamma)

            def counted_draft(*a):
                self._c_draft_compiles.inc()
                return dbase(*a)

            self._draft_step = jax.jit(counted_draft, donate_argnums=(1,))
            if not self._provider.shared_cache:
                # chunked draft-side context ingestion (logits discarded) so
                # long catch-ups ride prefill_chunk-bucketed shapes instead
                # of a one-shot full-context fused call
                ibase = self._provider.make_step()

                def counted_ingest(*a):
                    self._c_draft_compiles.inc()
                    return ibase(*a)

                self._draft_ingest = jax.jit(counted_ingest,
                                             donate_argnums=(1,))
            vbase = make_verify_step(cfg)

            def counted_verify(*a):
                self._c_verify_compiles.inc()
                return vbase(*a)

            self._verify_step = jax.jit(counted_verify, donate_argnums=(1,))

        # -- hardware cost attribution (repro.obs.hwcost) ---------------------
        # Per-token-pass prices by phase, fixed at init: prefill / decode /
        # verify run the full-precision model; draft and draft-side ingest
        # run at the provider's price (truncated bit-planes → exactly
        # proportionally fewer read cycles; own-artifact drafts get their
        # own cost table; layer-skip scales by cost_ratio).
        self.hw = hw if hw else None  # empty cost table ⇒ no attribution
        self._hw_prices: Dict[str, Tuple[float, float]] = {}
        self._hw_bs: Dict[str, Tuple[float, float]] = {}
        self._hw_draft: Optional[Dict[str, Any]] = None
        if self.hw is not None:
            full = (self.hw.pj_per_token(), self.hw.ns_per_token())
            bs_full = (self.hw.bitslice_pj_per_token(),
                       self.hw.bitslice_ns_per_token())
            for ph in ("prefill", "decode", "verify"):
                self._hw_prices[ph] = full
                self._hw_bs[ph] = bs_full
            if self._provider is not None:
                dp = draft_price(self.hw, self._provider, self.params)
                self._hw_draft = dp
                for ph in ("draft", "draft_ingest"):
                    self._hw_prices[ph] = (dp["pj"], dp["ns"])
                    self._hw_bs[ph] = (dp["bs_pj"], dp["bs_ns"])
            self._c_hw_tokens = reg.counter(
                "hw_tokens", "token-passes priced by the DA hardware model")
            self._c_hw_pj = reg.counter(
                "hw_est_pj", "estimated DA energy of executed work (pJ)")
            self._c_hw_ns = reg.counter(
                "hw_est_ns",
                "estimated serialized DA latency of executed work (ns)")
            self._h_req_pj = reg.histogram(
                "req_hw_pj", "per-request estimated DA energy (pJ)",
                buckets=ENERGY_BUCKETS)

    # -- registry-backed counter views ---------------------------------------
    # The pre-registry attribute surface (tests and external tooling read
    # e.g. ``sched.steps``) kept alive as int views over the registry series.
    @property
    def steps(self) -> int:
        return int(self._c_steps.total)

    @property
    def out_tokens(self) -> int:
        return int(self._c_out.total)

    @property
    def ctx_tokens(self) -> int:
        return int(self._c_ctx.total)

    @property
    def preemptions(self) -> int:
        return int(self._c_preempt.total)

    @property
    def step_compiles(self) -> int:
        return int(self._c_compiles.total)

    @property
    def prefix_lookups(self) -> int:
        return int(self._c_pref_lookups.total)

    @property
    def prefix_hits(self) -> int:
        return int(self._c_pref_hits.total)

    @property
    def cow_copies(self) -> int:
        return int(self._c_cow.total)

    @property
    def draft_steps(self) -> int:
        return int(self._c_draft_steps.total)

    @property
    def verify_steps(self) -> int:
        return int(self._c_verify_steps.total)

    @property
    def spec_rounds(self) -> int:
        return int(self._c_spec_rounds.total)

    @property
    def drafted_tokens(self) -> int:
        return int(self._c_drafted.total)

    @property
    def accepted_drafts(self) -> int:
        return int(self._c_accepted.total)

    @property
    def bonus_tokens(self) -> int:
        return int(self._c_bonus.total)

    @property
    def spec_disabled(self) -> int:
        return int(self._c_spec_off.total)

    @property
    def draft_compiles(self) -> int:
        return int(self._c_draft_compiles.total)

    @property
    def verify_compiles(self) -> int:
        return int(self._c_verify_compiles.total)

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        t0 = len(req.prompt)
        if t0 >= self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt of {t0} tokens does not fit "
                f"max_len={self.max_len}"
            )
        worst = self._worst_pages(t0 + len(req.generated), req.max_new_tokens
                                  - len(req.generated))
        if worst > self.pool.n_pages - 1:
            raise ValueError(
                f"request {req.uid} can never be served: needs {worst} pages "
                f"but the pool holds {self.pool.n_pages - 1}"
            )
        req.submit_t = time.perf_counter()
        self.queue.append(req)
        if self._tr.enabled:
            self._tr.instant("submit", request_track(req.uid),
                             ts=req.submit_t, prompt_tokens=t0,
                             max_new_tokens=req.max_new_tokens)

    def _worst_pages(self, ctx_len: int, rem_new: int) -> int:
        return pages_for(min(ctx_len + max(rem_new, 0), self.max_len),
                         self.page_size)

    def _lane_reservation(self, lane: _Lane) -> int:
        return self._worst_pages(
            len(lane.ctx), lane.req.max_new_tokens - len(lane.req.generated)
        )

    def _admit(self) -> None:
        for i in range(self.b):
            if not self.queue:
                return
            if self.lanes[i] is not None:
                continue
            req = self.queue[0]
            ctx = list(int(t) for t in req.prompt) + list(req.generated)
            hit_nodes, hit = ([], 0)
            if self.prefix is not None:
                hit_nodes, hit = self.prefix.match(ctx)
            # a hit mid-page means the lane's first write COWs the last
            # shared page — one extra allocation the reservation must carry
            cow_extra = 1 if hit % self.page_size else 0
            if self.admission == "reserve":
                held = sum(self._lane_reservation(l)
                           for l in self.lanes if l is not None)
                # discount only hit pages a RUNNING lane also holds (those
                # are already inside `held`, so the shared page would be
                # counted twice); trie-only hit pages occupy pool capacity
                # no reservation covers, so they stay in this lane's worst —
                # the reserve invariant (worst-case always fits) survives
                live = {p for l in self.lanes if l is not None
                        for p in l.pages}
                discount = sum(1 for nd in hit_nodes if nd.page in live)
                worst = (self._worst_pages(
                    len(ctx), req.max_new_tokens - len(req.generated))
                    - discount + cow_extra)
                if held + worst > self.pool.n_pages - 1:
                    return  # backpressure: head-of-line waits for pages
            else:
                # optimistic: first chunk must fit now, plus a few headroom
                # pages for decode growth (anti-thrash watermark — without
                # it a preempted request is re-admitted next tick and
                # preempted again, replaying its prefill forever). A
                # PREEMPTED request re-admits only when its whole
                # accumulated context fits: resuming it on a first-chunk
                # sliver would just replay-and-evict in a loop.  Cached
                # prefix pages are already resident: only the uncovered
                # remainder needs fresh pages.
                need = (len(ctx) - hit if req.uid in self._preempted
                        else min(len(ctx) - hit, self.prefill_chunk))
                headroom = max(2, self.pool.n_pages // 16)
                # cap at pool capacity: a request whose ctx+headroom exceeds
                # the whole pool must still admit once the pool drains, or
                # it would wait forever on a condition that cannot occur
                want = min(pages_for(hit + need, self.page_size)
                           - len(hit_nodes) + cow_extra + headroom,
                           self.pool.n_pages - 1 - len(hit_nodes))
                if not self._can_cover(want):
                    return
                self._preempted.discard(req.uid)
            self.queue.pop(0)
            pages: List[int] = []
            if self.prefix is not None:
                self._c_pref_lookups.inc()
                # denominator of hit_rate: prompt tokens only — generated
                # tokens of a re-admitted preempted request are never
                # cacheable, so counting them would deflate the rate
                self.prefix.lookup_tokens += len(req.prompt)
                if hit_nodes:
                    pages = self.prefix.claim(hit_nodes, self.pool)
                    self._c_pref_hits.inc()
                    self.prefix.cached_tokens += hit
            lane = _Lane(req=req, pages=pages, ctx=ctx, pos=hit,
                         admitted_t=time.perf_counter())
            self.lanes[i] = lane
            wait = lane.admitted_t - req.submit_t
            self._h_queue.observe(wait)
            if self._tr.enabled:
                # one "running" span per residency period: begun here, ended
                # by _preempt or the finish paths — span balance over a
                # drained run is a tested invariant
                self._tr.begin("running", request_track(req.uid),
                               ts=lane.admitted_t, lane=i, ctx_tokens=len(ctx),
                               prefix_hit_tokens=hit, queue_wait_s=wait)

    # -- preemption / eviction -----------------------------------------------
    def _preempt(self, i: int) -> None:
        """Evict lane i back to the queue head: pages freed now, KV rebuilt
        by replayed chunked prefill on re-admission (greedy decoding makes
        the replay token-exact)."""
        lane = self.lanes[i]
        self.pool.free(lane.pages)
        self.queue.insert(0, lane.req)
        self._preempted.add(lane.req.uid)
        self.lanes[i] = None
        self._c_preempt.inc()
        if self._tr.enabled:
            track = request_track(lane.req.uid)
            self._tr.instant("preempt", track,
                             generated=len(lane.req.generated))
            self._tr.end("running", track)

    def _youngest_other(self, i: int) -> Optional[int]:
        cands = [(j, l) for j, l in enumerate(self.lanes)
                 if l is not None and j != i]
        if not cands:
            return None
        return max(cands, key=lambda t: t[1].admitted_t)[0]

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Pool allocation with prefix-cache spill: on exhaustion, evict LRU
        trie nodes (cached prefixes nobody is running) before giving up."""
        got = self.pool.alloc(n)
        if (got is None and self.prefix is not None
                and self.prefix.evict_until(self.pool, n)):
            got = self.pool.alloc(n)
        return got

    def _can_cover(self, n: int) -> bool:
        """Could ``n`` pages be produced right now (free + evictable)?"""
        free = self.pool.free_pages
        if self.prefix is not None:
            free += self.prefix.reclaimable(self.pool)
        return n <= free

    def _cow_shared_page(self, lane: _Lane) -> bool:
        """Copy-on-write guard, called before KV rows are written at
        ``lane.pos``: if that position lands in a page other owners (the
        prefix trie, or lanes sharing the prefix) still reference, give the
        lane a private copy first — a write into a shared page would corrupt
        every other reader's KV. Only the last, partially-consumed page of a
        prefix hit can be in this state; pages past it are always exclusive.
        Returns False when no page can be found for the copy (backpressure).
        """
        if self.prefix is None:
            return True
        idx = lane.pos // self.page_size
        if idx >= len(lane.pages):
            return True
        src = lane.pages[idx]
        if self.pool.refcount(src) <= 1:
            return True
        got = self._alloc(1)
        if got is None:
            return False
        dst = got[0]
        if self.draft_caches is not None:
            # an own-cache draft provider indexes its pools with the SAME
            # page tables — its copy rides the same COW
            both = copy_page({"t": self.caches, "d": self.draft_caches},
                             src, dst)
            self.caches, self.draft_caches = both["t"], both["d"]
        else:
            self.caches = copy_page(self.caches, src, dst)
        lane.pages[idx] = dst
        self.pool.free([src])  # drop the lane's reference on the shared page
        self._c_cow.inc()
        return True

    def _maybe_cache_prefix(self, lane: _Lane) -> None:
        """Offer a lane's prompt pages to the trie once the prompt is fully
        ingested (every full prompt page then holds valid KV)."""
        if (self.prefix is None or lane.cached
                or lane.pos < len(lane.req.prompt)):
            return
        lane.cached = True
        self.prefix.insert(lane.ctx[: len(lane.req.prompt)], lane.pages,
                           self.pool)

    def _ensure_pages(self, lane: _Lane, n: int) -> int:
        """Grow lane.pages to cover pos+n tokens; returns the n actually
        covered — a prefill chunk shrinks to what free pages allow, 0 means
        fully deferred (backpressure, not a crash)."""
        if n > 0 and not self._cow_shared_page(lane):
            return 0
        while n > 0:
            need = pages_for(lane.pos + n, self.page_size) - len(lane.pages)
            if need <= 0:
                return n
            got = self._alloc(need)
            if got is not None:
                lane.pages.extend(got)
                return n
            fit = ((len(lane.pages) + self.pool.free_pages) * self.page_size
                   - lane.pos)
            n = min(n - 1, max(fit, 0))
        return 0

    # -- the tick ------------------------------------------------------------
    def step(self) -> int:
        """One scheduler tick: admit, run a compact chunked-prefill sub-batch
        (if any lane is still ingesting its prompt), then one pure decode
        step over the full batch. Returns the number of active lanes.

        With tracing on, the tick is the ``sched.tick`` span and each host
        phase inside it a ``sched.*`` span (``TraceRecorder.phase``): admit,
        then per device call pack → dispatch → wait → d2h → sample, then
        retire."""
        if not self.queue and all(l is None for l in self.lanes):
            return 0
        with self._tr.phase("sched.tick"):
            return self._tick()

    def _tick(self) -> int:
        with self._tr.phase("sched.admit"):
            self._admit()
        active = [(i, l) for i, l in enumerate(self.lanes) if l is not None]
        if not active:
            return 0
        if self._start_t is None:
            self._start_t = time.perf_counter()
        self._c_steps.inc()
        t_tick = time.perf_counter()
        allocs0, cow0 = self.pool._allocs, self._c_cow.total
        evict0 = self.prefix.evictions if self.prefix is not None else 0

        progressed: set = set()
        decode_count = sum(1 for _, l in active if l.remaining == 1)
        prefill = [(i, l) for i, l in active if l.remaining > 1]
        if prefill:
            progressed |= self._prefill_phase(prefill, decode_count)
        decode = [(i, l) for i, l in enumerate(self.lanes)
                  if l is not None and l.remaining == 1]
        if decode:
            staged, plain = self._partition_spec(decode)
            if staged:
                progressed |= self._spec_phase(staged)
            if plain:
                progressed |= self._decode_phase(plain)

        with self._tr.phase("sched.retire"):
            active = [(i, l) for i, l in enumerate(self.lanes)
                      if l is not None]
            if active and not progressed:
                # pool jammed: keep only the oldest lane (guaranteed
                # servable by the submit-time capacity check), requeue the
                # rest
                oldest = min(active, key=lambda t: t[1].admitted_t)[0]
                for i, _ in active:
                    if i != oldest:
                        self._preempt(i)
            for i, l in ((i, l) for i, l in enumerate(self.lanes)
                         if l is not None):
                if i in progressed:
                    l.stalled_steps = 0
                else:
                    l.stalled_steps += 1
                    if l.stalled_steps > self.stall_patience:
                        self._preempt(i)  # stalled: hand its pages to the rest
            live = sum(l is not None for l in self.lanes)
            now = time.perf_counter()
            self._h_tick.observe(now - t_tick)
            self._g_lanes.set(live)
            self._g_queue.set(len(self.queue))
            self._g_used_pages.set(self.pool.used_pages)
            if self._tr.enabled:
                evict1 = (self.prefix.evictions if self.prefix is not None
                          else 0)
                self._tr.complete(
                    "tick", SCHED_TRACK, t_tick, now - t_tick,
                    lanes=live, decode_lanes=decode_count,
                    prefill_lanes=len(prefill), queue=len(self.queue),
                    pages_allocated=self.pool._allocs - allocs0,
                    cow_copies=int(self._c_cow.total - cow0),
                    prefix_evictions=evict1 - evict0,
                    used_pages=self.pool.used_pages)
            return live

    def _submit_plan(self, phase: str, rows, poss) -> None:
        """analysis_debug gate: prove the page-aliasing invariants for one
        launch before it reaches the device.  ``rows`` = [(batch_row,
        lane_idx, lane)]; ``poss`` maps lane index -> the token positions
        this launch writes for that lane.  Positions past the lane's page
        table (the pad position) land in the garbage page on device, and
        the checker exempts garbage-page aliasing by design."""
        from repro.analysis.races import PageWrite, TickPlan, assert_plan_ok

        ps = self.page_size
        writes = []
        for _, i, l in rows:
            for pos in poss[i]:
                pi = pos // ps
                page = l.pages[pi] if 0 <= pi < len(l.pages) else GARBAGE_PAGE
                writes.append(PageWrite(
                    lane=i, uid=l.req.uid, page=page, offset=pos % ps))
        touched = {w.page for w in writes}
        plan = TickPlan.build(
            phase=phase, page_size=ps, writes=writes,
            refcounts={p: self.pool.refcount(p) for p in touched
                       if 0 <= p < self.pool.n_pages},
            trie_pages=self.prefix.pages() if self.prefix is not None else (),
            free_pages=self.pool.free_page_ids(),
            garbage_page=GARBAGE_PAGE,
        )
        assert_plan_ok(plan)
        self.plans_checked += 1

    def _pack_step(self, rows, plan, n_rows: int, t_step: int,
                   phase: str):
        """The device arguments of one call of the unified step for ``rows``
        = [(batch_row, lane_idx, lane)], each lane advancing ``plan[i]``
        tokens from ``lane.pos``. Pad rows/columns carry the garbage
        position, so their writes land in the garbage page and every real
        row's ``kpos <= tpos`` mask excludes them."""
        toks = {i: l.ctx[l.pos : l.pos + plan[i]] for _, i, l in rows}
        poss = {i: range(l.pos, l.pos + plan[i]) for _, i, l in rows}
        if self.analysis_debug:
            self._submit_plan(phase, rows, poss)
        self._c_attn_pages.inc(sum(len(l.pages) for _, _, l in rows),
                               phase=phase, kind="live")
        self._c_attn_pages.inc(n_rows * self.W, phase=phase, kind="table")
        args = self._pack_rows(rows, toks, poss, n_rows, t_step, self.cfg)
        if self.greedy:
            return args
        # each row's key is that of the 64-bit seed (uid << 20) + tokens
        # generated so far, the index of the token it samples; pad rows
        # draw from key 0
        seeds = np.zeros((n_rows,), np.uint64)
        for r, _, l in rows:
            seeds[r] = (l.req.uid << 20) + len(l.req.generated)
        return args + (jnp.asarray(sample_keys(seeds)),)

    def _dispatch(self, name: str, phase: str, fn, *args):
        """Issue one device call under its own span (``name`` carries the
        call's [rows x T] shape, which pairs it with its executable in a
        profiler capture)."""
        self._c_calls.inc(phase=phase)
        with self._tr.phase(name):
            return fn(*args)

    def _read_back(self, out, phase: str) -> np.ndarray:
        """The call's synchronisation point, in two spans: ``sched.wait``
        until the device has computed ``out``, then ``sched.d2h`` for the
        copy to the host (a plain step's ``out`` is its int32 token ids)."""
        with self._tr.phase("sched.wait"):
            jax.block_until_ready(out)
        with self._tr.phase("sched.d2h", bytes=out.nbytes):
            host = np.asarray(out)
        self._c_d2h.inc(host.nbytes, phase=phase)
        return host

    def _hw_charge(self, req: Request, phase: str, n: int) -> float:
        """Price ``n`` executed token-passes of ``phase`` work on the DA
        hardware model: registry counters (labeled by phase) plus the
        request's own running total.  Returns the pJ charged (0.0 with no
        cost model attached) — callers may stamp it on trace spans.  Purely
        host-side float math; never touches device state, so accounting is
        identical with tracing on or off."""
        if self.hw is None or n <= 0:
            return 0.0
        pj_tok, ns_tok = self._hw_prices[phase]
        pj, ns = pj_tok * n, ns_tok * n
        self._c_hw_tokens.inc(n, phase=phase)
        self._c_hw_pj.inc(pj, phase=phase)
        self._c_hw_ns.inc(ns, phase=phase)
        req.hw_pj += pj
        req.hw_ns += ns
        return pj

    def _prefill_phase(self, prefill, decode_count: int) -> set:
        """Up to ``prefill_lanes`` ingesting lanes advance by one chunk each
        in a compact [prefill_lanes, T_bucket] sub-batch — the page pool is
        batch-free, so prefill never has to ride (and widen) the decode
        batch. The token budget is what's left after the decode lanes take
        their 1 token each."""
        # with no decode lanes, budget == token_budget >= 1 here, so prefill
        # always advances
        budget = self.token_budget - decode_count
        if budget <= 0 and decode_count > 0:
            return set()  # decode saturates the budget this tick
        with self._tr.phase("sched.pack", phase="prefill") as span:
            sel = sorted(prefill, key=lambda t: t[1].admitted_t)
            sel = sel[: self.prefill_lanes]
            plan: Dict[int, int] = {}
            for i, l in sel:
                n = min(l.remaining, self.prefill_chunk, budget)
                # may shrink or defer: backpressure
                n = self._ensure_pages(l, n)
                plan[i] = n
                budget -= n
            rows = [(r, i, l) for r, (i, l) in enumerate(
                (i, l) for i, l in sel if plan[i] > 0)]
            if not rows:
                return set()
            # cap at prefill_chunk so a non-pow2 chunk size uses the shape
            # warmup() compiled, not a one-off pow2 round-up
            t_step = min(pow2_bucket(max(plan[i] for _, i, _ in rows)),
                         self.prefill_chunk)
            span.update(rows=len(rows), t_step=t_step)
            t0 = time.perf_counter()
            args = self._pack_step(rows, plan, self.prefill_lanes, t_step,
                                   "prefill")
        ids, self.caches = self._dispatch(
            f"paged_step[{self.prefill_lanes}x{t_step}]", "prefill",
            self._step, self.params, self.caches, *args)
        ids = self._read_back(ids, "prefill")
        now = time.perf_counter()
        with self._tr.phase("sched.sample", phase="prefill"):
            if self._tr.enabled:
                for r, i, l in rows:
                    extra = ({"est_pj": self._hw_prices["prefill"][0]
                              * plan[i]} if self.hw is not None else {})
                    self._tr.complete(
                        "prefill_chunk", request_track(l.req.uid), t0,
                        now - t0, tokens=plan[i], pos=l.pos, **extra)
                self._tr.complete("prefill", SCHED_TRACK, t0, now - t0,
                                  lanes=len(rows), t_step=t_step)
            for r, i, l in rows:
                l.pos += plan[i]
                self._hw_charge(l.req, "prefill", plan[i])
                self._c_ctx.inc(plan[i])
                # before _sample can free the pages
                self._maybe_cache_prefix(l)
                if l.remaining == 0:  # chunk covered the last unseen token
                    self._sample(i, l, ids[r : r + 1], now)
        return {i for _, i, _ in rows}

    def _decode_phase(self, decode) -> set:
        """All decoding lanes advance one token in a [batch, 1] step; a lane
        that cannot get its next page preempts the youngest other lane."""
        with self._tr.phase("sched.pack", phase="decode") as span:
            ready = set()
            for i, l in sorted(decode, key=lambda t: t[1].admitted_t):
                if self.lanes[i] is not l:
                    continue  # preempted as a victim earlier in this loop
                got = self._ensure_pages(l, 1)
                while got == 0:
                    victim = self._youngest_other(i)
                    if victim is None:
                        break
                    self._preempt(victim)
                    got = self._ensure_pages(l, 1)
                if got:
                    ready.add(i)
            live = [(i, l) for i, l in decode
                    if i in ready and self.lanes[i] is l]
            if not live:
                return set()
            plan = {i: 1 for i, _ in live}
            # lanes compact into a bucketed width (requests own pages, not
            # batch rows, so a half-empty batch never pays full-width compute)
            width = width_bucket(len(live), self.b)
            rows = [(r, i, l) for r, (i, l) in enumerate(live)]
            span.update(rows=len(rows), t_step=1)
            t0 = time.perf_counter()
            args = self._pack_step(rows, plan, width, 1, "decode")
        ids, self.caches = self._dispatch(
            f"paged_step[{width}x1]", "decode", self._step, self.params,
            self.caches, *args)
        ids = self._read_back(ids, "decode")
        now = time.perf_counter()
        with self._tr.phase("sched.sample", phase="decode"):
            if self._tr.enabled:
                extra = ({"est_pj": self._hw_prices["decode"][0] * len(live)}
                         if self.hw is not None else {})
                self._tr.complete("decode", SCHED_TRACK, t0, now - t0,
                                  lanes=len(live), width=width, **extra)
            for r, i, l in rows:
                l.pos += 1
                self._hw_charge(l.req, "decode", 1)
                self._c_ctx.inc()
                # before _sample can free the pages
                self._maybe_cache_prefix(l)
                self._sample(i, l, ids[r : r + 1], now)
        return {i for i, _ in live}

    # -- speculative decoding ------------------------------------------------
    def _fresh_spec_state(self) -> Dict[str, Any]:
        return {"on": True, "ema": None, "rounds": 0}

    def _partition_spec(self, decode):
        """Split decode lanes into spec-staged and plain.

        A lane speculates when its request's speculation is still on, it can
        still emit ≥ 2 tokens (otherwise a round cannot beat one decode
        step), the gamma+1 verify window stays inside the addressable page
        table, and the extra pages stage in one shot — page shortage demotes
        the lane to plain decode for this tick (the plain path owns the
        preemption machinery).  Staging snapshots a page checkpoint FIRST so
        the round's growth is fully attributable and rollback-exact.
        """
        if self.spec is None:
            return [], decode
        g = self.spec.gamma
        addressable = (self.W - 1) * self.page_size
        staged, plain = [], []
        for i, l in sorted(decode, key=lambda t: t[1].admitted_t):
            st = self._spec_state.setdefault(l.req.uid,
                                             self._fresh_spec_state())
            allowance = min(l.req.max_new_tokens - len(l.req.generated),
                            self.max_len - len(l.ctx))
            ok = (st["on"] and allowance >= 2
                  and l.pos + g + 1 <= addressable)
            if ok:
                ck = kv_checkpoint(self.pool, l.pages)
                # drafts must never roll back (or write into) a SHARED page:
                # COW the last partial prefix-hit page before any draft KV
                # lands, so rollback only ever touches exclusively-owned
                # growth (page shortage demotes the lane to plain decode)
                if not self._cow_shared_page(l):
                    ok = False
                need = pages_for(l.pos + g + 1, self.page_size) - len(l.pages)
                if ok and need > 0:
                    got = self._alloc(need)
                    if got is None:
                        ok = False
                    else:
                        l.pages.extend(got)
                if ok:
                    staged.append((i, l, ck))
            if not ok:
                plain.append((i, l))
        return staged, plain

    def _pack_rows(self, rows, toks, poss, n_rows: int, t_step: int, cfg):
        """Assemble one fixed-shape batch from per-lane token/position lists
        (pad rows/columns carry the garbage position) and upload it: the
        step's (tokens, positions, page table, last_idx) on the device."""
        tokens = np.zeros((n_rows, t_step), np.int32)
        positions = np.full((n_rows, t_step), self.pad_pos, np.int32)
        last_idx = np.zeros((n_rows,), np.int32)
        table = np.full((n_rows, self.W), GARBAGE_PAGE, np.int32)
        for r, i, l in rows:
            seq = toks[i]
            n = len(seq)
            tokens[r, :n] = seq
            positions[r, :n] = poss[i]
            last_idx[r] = n - 1
            table[r, : len(l.pages)] = l.pages
        return (jnp.asarray(tokens), mk_positions(cfg, jnp.asarray(positions)),
                jnp.asarray(table), jnp.asarray(last_idx))

    def _run_draft(self, rows, toks, poss, width: int,
                   t_step: int) -> np.ndarray:
        """One fused draft call → all gamma proposals [width, gamma]."""
        if self.analysis_debug and self._provider.shared_cache:
            # the fused call feeds poss[i] then scans gamma-1 single-token
            # steps, each writing the next position — the full write span is
            # poss[i] plus (gamma - 1) positions past its end.  Own-cache
            # providers write the draft pool, whose ledger the target pool's
            # refcounts/trie do not govern (catch-up deliberately rewrites
            # shared-prefix draft rows; the rewrite is idempotent).
            g = self.spec.gamma
            self._submit_plan("spec_draft", rows, {
                i: list(poss[i]) + [poss[i][-1] + 1 + k for k in range(g - 1)]
                for _, i, _ in rows})
        with self._tr.phase("sched.pack", phase="spec_draft",
                            rows=len(rows), t_step=t_step):
            args = self._pack_rows(rows, toks, poss, width, t_step,
                                   self._provider.cfg)
        caches = (self.caches if self._provider.shared_cache
                  else self.draft_caches)
        drafts, new = self._dispatch(
            f"spec_draft[{width}x{t_step}]", "spec_draft", self._draft_step,
            self._provider.params, caches, *args)
        if self._provider.shared_cache:
            self.caches = new
        else:
            self.draft_caches = new
        self._c_draft_steps.inc(self.spec.gamma)
        return self._read_back(drafts, "spec_draft")

    def _run_ingest(self, rows, toks, poss, width: int, t_step: int) -> None:
        """Draft-side context ingestion: its logits stay on the device."""
        with self._tr.phase("sched.pack", phase="spec_ingest",
                            rows=len(rows), t_step=t_step):
            args = self._pack_rows(rows, toks, poss, width, t_step,
                                   self._provider.cfg)
        _, self.draft_caches = self._dispatch(
            f"spec_ingest[{width}x{t_step}]", "spec_ingest",
            self._draft_ingest, self._provider.params, self.draft_caches,
            *args)

    def _draft_catch_up(self, rows) -> None:
        """Own-cache providers only: chunked ingestion of the context the
        draft model has not seen (first spec round after admission or
        preemption).  Feeds prefill_chunk-bucketed slices through the draft
        step — the target side deliberately chunks its prefill for the same
        reason, and the fused draft call afterwards always runs at its
        small warmed shapes, never a one-shot full-context feed."""
        chunk = self.prefill_chunk
        while True:
            pend = [(i, l) for _, i, l in rows
                    if l.pos - l.draft_pos >= chunk]
            if not pend:
                return
            toks: Dict[int, List[int]] = {}
            poss: Dict[int, List[int]] = {}
            for i, l in pend:
                n = min(chunk, l.pos - l.draft_pos)
                toks[i] = list(l.ctx[l.draft_pos : l.draft_pos + n])
                poss[i] = list(range(l.draft_pos, l.draft_pos + n))
            t = min(pow2_bucket(max(len(x) for x in toks.values())), chunk)
            sub = [(r, i, l) for r, (i, l) in enumerate(pend)]
            self._run_ingest(sub, toks, poss,
                             width_bucket(len(pend), self.b), t)
            for i, l in pend:
                l.draft_pos += len(toks[i])
                self._hw_charge(l.req, "draft_ingest", len(toks[i]))

    def _run_verify(self, rows, toks, poss, width: int,
                    t_step: int) -> np.ndarray:
        if self.analysis_debug:
            self._submit_plan("spec_verify", rows, poss)
        with self._tr.phase("sched.pack", phase="spec_verify",
                            rows=len(rows), t_step=t_step):
            tokens, positions, table, _ = self._pack_rows(
                rows, toks, poss, width, t_step, self.cfg)
        logits, self.caches = self._dispatch(
            f"spec_verify[{width}x{t_step}]", "spec_verify",
            self._verify_step, self.params, self.caches, tokens, positions,
            table)
        self._c_verify_steps.inc()
        return self._read_back(logits, "spec_verify")  # [width, t_step, V]

    def _spec_phase(self, staged) -> set:
        """One speculative round for the staged lanes: gamma batched draft
        steps (the first coalesces any draft-side catch-up), ONE batched
        full-precision verify over the gamma+1 window, greedy acceptance,
        then page rollback so rejected drafts leave no trace."""
        g = self.spec.gamma
        rows = [(r, i, l) for r, (i, l, _) in enumerate(staged)]
        ckpts = {i: ck for i, _, ck in staged}
        width = width_bucket(len(rows), self.b)
        shared = self._provider.shared_cache
        toks: Dict[int, List[int]] = {}
        poss: Dict[int, List[int]] = {}
        drafts: Dict[int, List[int]] = {}
        start_pos: Dict[int, int] = {}
        t0 = time.perf_counter()
        # one fused draft call: catch-up feed (own-cache providers ingest
        # what the target accepted since their last round; anything longer
        # than a prefill chunk was pre-ingested in bucketed slices) + gamma
        # greedy proposals scanned on-device
        if not shared:
            self._draft_catch_up(rows)
        for _, i, l in rows:
            start_pos[i] = l.pos
            s = l.pos if shared else min(l.draft_pos, l.pos)
            toks[i] = list(l.ctx[s : l.pos + 1])
            poss[i] = list(range(s, l.pos + 1))
        t1 = min(pow2_bucket(max(len(t) for t in toks.values())),
                 max(self.prefill_chunk, 1))
        # per-lane draft work this round: the fused call feeds len(toks[i])
        # tokens (catch-up + x_t, yielding the first proposal) then scans
        # gamma-1 more single-token steps — capture before toks is rebuilt
        # for verify below
        feed = {i: len(toks[i]) for _, i, _ in rows}
        dmat = self._run_draft(rows, toks, poss, width, t1)
        for r, i, _ in rows:
            drafts[i] = [int(t) for t in dmat[r]]
        # one batched verify over [x_t, d_1..d_g] — full precision, logits
        # at every position, exact KV overwrites the draft-quality rows
        for _, i, l in rows:
            toks[i] = [l.ctx[start_pos[i]]] + drafts[i]
            poss[i] = list(range(start_pos[i], start_pos[i] + g + 1))
        vlogits = self._run_verify(rows, toks, poss, width,
                                   pow2_bucket(g + 1))
        now = time.perf_counter()
        out = set()
        with self._tr.phase("sched.sample", phase="spec"):
            for r, i, l in rows:
                verify = [int(np.argmax(vlogits[r, j])) for j in range(g + 1)]
                m = greedy_accept(drafts[i], verify)
                # charge the round's executed work BEFORE _accept_tokens: a
                # lane finishing mid-round observes req_hw_pj with this round
                # included
                round_pj = (self._hw_charge(l.req, "draft", feed[i] + g - 1)
                            + self._hw_charge(l.req, "verify", g + 1))
                emitted = self._accept_tokens(i, l, verify[:m], now)
                l.pos = start_pos[i] + emitted
                # own-cache draft KV is valid for the matched prefix only
                l.draft_pos = min(start_pos[i] + g, l.pos)
                self._c_ctx.inc(emitted)
                self._c_spec_rounds.inc()
                self._c_drafted.inc(g)
                self._c_accepted.inc(m - 1)
                if m == g + 1:
                    self._c_bonus.inc()
                if self._tr.enabled:
                    extra = ({"est_pj": round_pj}
                             if self.hw is not None else {})
                    self._tr.complete("spec_round", request_track(l.req.uid),
                                      t0, now - t0, drafted=g, accepted=m - 1,
                                      emitted=emitted, **extra)
                self._update_spec_state(l.req.uid, (m - 1) / g)
                if self.lanes[i] is l:  # still running: release rejected pages
                    kv_rollback(self.pool, l.pages, ckpts[i],
                                keep=pages_for(l.pos, self.page_size))
                    self._maybe_cache_prefix(l)
                out.add(i)
        return out

    def _accept_tokens(self, i: int, lane: _Lane, tokens, now: float) -> int:
        """Emit verified tokens in order (stream callbacks, timing, finish
        checks); returns how many were emitted before a finish condition."""
        req = lane.req
        emitted = 0
        for tok in tokens:
            if not req.generated:
                req.first_token_t = now
                self._h_ttft.observe(now - req.submit_t)
            elif req.token_times:
                self._h_itl.observe(now - req.token_times[-1])
            req.token_times.append(now)
            req.generated.append(tok)
            lane.ctx.append(tok)
            emitted += 1
            self._c_out.inc()
            if self._tr.enabled:
                # stamped with the SAME clock value written to token_times,
                # so trace-derived TTFT/ITL equal latency_metrics() exactly
                self._tr.instant("token", request_track(req.uid), ts=now,
                                 n=len(req.generated))
            if req.on_token is not None:
                req.on_token(req.uid, tok)
            if (tok == req.eos_id
                    or len(req.generated) >= req.max_new_tokens
                    or len(lane.ctx) >= self.max_len):
                req.finish_t = now
                self.pool.free(lane.pages)
                self.done[req.uid] = req
                self.lanes[i] = None
                if self.hw is not None:
                    self._h_req_pj.observe(req.hw_pj)
                if self._tr.enabled:
                    track = request_track(req.uid)
                    self._tr.instant("finish", track, ts=now,
                                     tokens=len(req.generated))
                    self._tr.end("running", track, ts=now)
                break
        return emitted

    def _update_spec_state(self, uid: int, rate: float) -> None:
        """Per-request acceptance EMA; below-breakeven requests stop
        speculating (draft effort would cost more than it saves)."""
        st = self._spec_state[uid]
        a = self.spec.ema_alpha
        st["ema"] = rate if st["ema"] is None else a * rate + (1 - a) * st["ema"]
        st["rounds"] += 1
        if (st["on"] and st["rounds"] >= self.spec.warmup_rounds
                and st["ema"] < self._spec_floor):
            st["on"] = False
            self._c_spec_off.inc()

    def _sample(self, i: int, lane: _Lane, row: np.ndarray, now: float) -> None:
        """Emit the token of ``row``, lane ``i``'s row of the step's result
        (one int32, the id the step sampled): stamp its time, stream it,
        and retire the lane if it finished."""
        req = lane.req
        tok = int(row[0])
        if not req.generated:
            req.first_token_t = now
            self._h_ttft.observe(now - req.submit_t)
        elif req.token_times:
            self._h_itl.observe(now - req.token_times[-1])
        req.token_times.append(now)
        req.generated.append(tok)
        lane.ctx.append(tok)
        self._c_out.inc()
        if self._tr.enabled:
            # same clock value as token_times → exact TTFT/ITL reconstruction
            self._tr.instant("token", request_track(req.uid), ts=now,
                             n=len(req.generated))
        if req.on_token is not None:
            req.on_token(req.uid, tok)
        finished = (
            tok == req.eos_id
            or len(req.generated) >= req.max_new_tokens
            or len(lane.ctx) >= self.max_len
        )
        if finished:
            req.finish_t = now
            self.pool.free(lane.pages)
            self.done[req.uid] = req
            self.lanes[i] = None
            if self.hw is not None:
                self._h_req_pj.observe(req.hw_pj)
            if self._tr.enabled:
                track = request_track(req.uid)
                self._tr.instant("finish", track, ts=now,
                                 tokens=len(req.generated))
                self._tr.end("running", track, ts=now)

    def run(self, max_steps: int = 100_000) -> Dict[int, Request]:
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        return self.done

    def warmup(self) -> int:
        """Pre-compile every step-shape bucket (decode widths × prefill
        chunk buckets). The dummy batches carry only pad rows, so writes
        land in the garbage page and no live request state is touched.
        Returns the number of shapes compiled."""
        shapes = [(w, 1) for w in width_buckets(self.b)]
        t = 1
        while t < self.prefill_chunk:
            shapes.append((self.prefill_lanes, t))
            t *= 2
        shapes.append((self.prefill_lanes, self.prefill_chunk))
        shapes = list(dict.fromkeys(shapes))
        for bw, ts in shapes:
            tokens = jnp.zeros((bw, ts), jnp.int32)
            positions = jnp.full((bw, ts), self.pad_pos, dtype=jnp.int32)
            table = jnp.full((bw, self.W), GARBAGE_PAGE, dtype=jnp.int32)
            last_idx = jnp.zeros((bw,), jnp.int32)
            keys = (() if self.greedy
                    else (jnp.zeros((bw, 2), jnp.uint32),))
            _, self.caches = self._step(
                self.params, self.caches, tokens,
                mk_positions(self.cfg, positions), table, last_idx, *keys,
            )
        n_spec = 0
        if self.spec is not None:
            # draft [w, 1] + verify [w, pow2(gamma+1)] per decode width
            tv = pow2_bucket(self.spec.gamma + 1)
            for bw in width_buckets(self.b):
                table = jnp.full((bw, self.W), GARBAGE_PAGE, dtype=jnp.int32)
                dcaches = (self.caches if self._provider.shared_cache
                           else self.draft_caches)
                _, new = self._draft_step(
                    self._provider.params, dcaches,
                    jnp.zeros((bw, 1), jnp.int32),
                    mk_positions(self._provider.cfg,
                                 jnp.full((bw, 1), self.pad_pos, jnp.int32)),
                    table, jnp.zeros((bw,), jnp.int32),
                )
                if self._provider.shared_cache:
                    self.caches = new
                else:
                    self.draft_caches = new
                _, self.caches = self._verify_step(
                    self.params, self.caches,
                    jnp.zeros((bw, tv), jnp.int32),
                    mk_positions(self.cfg,
                                 jnp.full((bw, tv), self.pad_pos, jnp.int32)),
                    table,
                )
                n_spec += 2
        return len(shapes) + n_spec

    # -- maintenance / observability -----------------------------------------
    def defrag(self) -> None:
        """Compact live pages to the pool's low-index prefix (the page tables
        move with them; decode output is unchanged).  An own-cache draft
        provider's pools are indexed by the SAME page tables, so they must
        move under the same remap — both trees ride one defrag call (the
        tables and pool free list are rewritten exactly once).  Trie-held
        prefix pages are live owners too: they remap alongside the tables,
        so cached prefixes keep hitting across a defrag."""
        tables = [l.pages for l in self.lanes if l is not None]
        if self.draft_caches is not None:
            both = defrag({"target": self.caches, "draft": self.draft_caches},
                          self.pool, tables, trie=self.prefix)
            self.caches, self.draft_caches = both["target"], both["draft"]
        else:
            self.caches = defrag(self.caches, self.pool, tables,
                                 trie=self.prefix)

    def metrics(self) -> Dict[str, Any]:
        wall = (time.perf_counter() - self._start_t) if self._start_t else 0.0
        spec = None
        if self.spec is not None:
            drafted = self.drafted_tokens
            spec = {
                "provider": self._provider.name,
                "gamma": self.spec.gamma,
                "cost_ratio": round(self._provider.cost_ratio, 4),
                "rounds": self.spec_rounds,
                "draft_steps": self.draft_steps,
                "verify_steps": self.verify_steps,
                "drafted_tokens": drafted,
                "accepted_drafts": self.accepted_drafts,
                "acceptance_rate": (self.accepted_drafts / drafted
                                    if drafted else 0.0),
                "bonus_tokens": self.bonus_tokens,
                "draft_compiles": self.draft_compiles,
                "verify_compiles": self.verify_compiles,
                "disable_floor": round(self._spec_floor, 4),
                "disabled_requests": self.spec_disabled,
                "enabled_requests": sum(
                    1 for s in self._spec_state.values() if s["on"]),
            }
        prefix = None
        if self.prefix is not None:
            pc = self.prefix
            prefix = {
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                # token-weighted: the share of admitted prompt tokens whose
                # KV came off cached pages instead of prefill compute
                "hit_rate": (pc.cached_tokens / pc.lookup_tokens
                             if pc.lookup_tokens else 0.0),
                "cached_tokens": pc.cached_tokens,
                "evictions": pc.evictions,
                "trie_pages": pc.n_pages,
                "cow_copies": self.cow_copies,
            }
        # KV storage pricing: what a resident token costs at this pool's
        # precision, and the capacity multiplier vs compute-dtype pages at
        # equal pool bytes (1.0 when every position runs the fp16 escape
        # hatch; ~itemsize(compute)*hd/(hd+2) per quantized position).
        bpt = sum(kv_token_bytes(self.cfg, dt)
                  for dt in self.kv_dtypes.values()) * self.cfg.n_periods
        fp_bpt = (kv_token_bytes(self.cfg, "fp16") * len(self.kv_dtypes)
                  * self.cfg.n_periods)
        # one source for byte accounting: the pool's own stats feed both the
        # "pool" section and the kv section's byte keys ("pool_bytes" stays
        # the measured device-array footprint, which the sharded caches can
        # pad past page_bytes * n_pages)
        # estimated cost of the run on the paper's DA hardware: the static
        # per-token table (summary) plus LIVE workload-weighted totals —
        # executed token-passes per phase × per-phase prices, with the
        # bit-slicing counterfactual priced over the SAME executed work so
        # the live ratios answer "what did this workload save"
        hw = None
        if self.hw is not None:
            hw = self.hw.summary()
            phases = sorted(self._hw_prices)
            tokens = {p: self._c_hw_tokens.value(phase=p) for p in phases}
            est_pj = {p: self._c_hw_pj.value(phase=p) for p in phases}
            est_ns = {p: self._c_hw_ns.value(phase=p) for p in phases}
            total_pj = sum(est_pj.values())
            total_ns = sum(est_ns.values())
            bs_pj = sum(self._hw_bs[p][0] * tokens[p] for p in phases)
            bs_ns = sum(self._hw_bs[p][1] * tokens[p] for p in phases)
            out_toks = self.out_tokens
            hw.update({
                "tokens": tokens,
                "est_pj": {**est_pj, "total": total_pj},
                "est_ns": {**est_ns, "total": total_ns},
                "pj_per_out_token": (total_pj / out_toks
                                     if out_toks else 0.0),
                "live": {
                    "da_pj": total_pj,
                    "bitslice_pj": bs_pj,
                    "energy_ratio": bs_pj / total_pj if total_pj else 0.0,
                    "da_ns": total_ns,
                    "bitslice_ns": bs_ns,
                    "latency_ratio": bs_ns / total_ns if total_ns else 0.0,
                },
            })
            if self._hw_draft is not None:
                hw["draft"] = dict(self._hw_draft)
        pool_stats = self.pool.stats()
        kv = {
            "kv_dtypes": dict(self.kv_dtypes),
            "bytes_per_token": bpt,
            "fp_bytes_per_token": fp_bpt,
            "capacity_multiplier": fp_bpt / bpt if bpt else 0.0,
            "page_bytes": pool_stats["page_bytes"],
            "used_bytes": pool_stats["used_bytes"],
            "free_bytes": pool_stats["free_bytes"],
            "pool_bytes": kv_cache_nbytes(self.caches),
        }
        return {
            **base_metrics("paged", self.done, self.out_tokens),
            "ctx_tokens": self.ctx_tokens,
            "steps": self.steps,
            "preemptions": self.preemptions,
            "step_compiles": self.step_compiles,
            "wall_s": wall,
            "tokens_per_s": self.out_tokens / wall if wall > 0 else 0.0,
            "pool": pool_stats,
            "kv": kv,
            "hw": hw,
            "spec": spec,
            "prefix_cache": prefix,
        }

"""Slot-based continuous batching over the device-resident decode loop.

Serving architecture
--------------------
The scheduler owns a fixed-width **slot table**: ``cfg.batch`` decode slots
that share one KV-cache allocation ([layers, B, max_len, ...]), one jitted
prefill/join step and one jitted multi-token decode scan.  Host state per
slot is just (request id, token budget, live length); device state is
(next-token [B,1], per-slot cache_len [B], done flag [B], remaining budget
[B], PRNG key, caches).

Refill policy: requests queue in a ``deque``.  Between decode *segments*
(``cfg.sync_every`` fused steps — the only host sync points), every retired
slot is refilled from the queue head: the joining prompts are padded to one
width, batch-prefilled in a single jitted call, and selected into the live
state with a batch-axis ``where`` — occupied slots keep their caches
bit-for-bit.  Mixed-length requests therefore share one jitted decode step
at all times instead of padding to a fresh batch each round, and the same
two compiled executables are reused across the whole drain (no retracing).

Retirement: a slot retires when it emits EOS (the EOS token is kept) or
exhausts its ``max_new`` budget.  Both conditions are evaluated *on device*
inside the scan (done-flag latch), so a retired slot stops sampling,
stops growing its cache and emits a PAD sentinel until the segment ends;
the host mirrors the same rules when it drains the emitted block.

Dead-block skipping (paper §5.1.2): commercial PIM kernels win by skipping
commands for banks whose data is dead; the serving analogue is KV blocks
past a slot's live length.  Two levels: (1) per-slot lengths reach the
decode-attention kernel, which skips every KV block past *that slot's*
depth before any compute; (2) between segments the host knows the deepest
live slot, so the engine re-jits the scan with a power-of-two ``kv_cap``
and the attention op slices the cache to that bound — blocks past *every*
slot's length are never launched at all.

Paged mode (``cfg.paged``, repro.serve.kvpool): the per-slot ``max_len``
stripes are replaced by fixed-size pages in one pooled allocation.
Admission is now on **free-page capacity** — a request joins when the pool
can hold its prompt + budget (``ceil((plen + max_new) / page_size)``
pages), not merely when a slot index is free — and a retiring slot returns
every page to the free list at the segment boundary, so short/early-EOS
requests stop stranding ``max_len``-sized stripes.  The dense ``kv_cap``
bucketing becomes **page-count bucketing**: the device page table is
sliced to a power-of-two bound on the deepest live slot's page count
(same ``_pow2_bucket`` policy, so segments don't retrace), which prunes
the paged-attention grid to live pages only.

Prefix cache (``cfg.prefix_cache``, repro.serve.prefixcache, needs paged):
admission first matches the prompt against a radix tree of page-aligned
cached chunks; the matched pages are mapped into the joining slot via
``KVPool.share`` (refcounts go above 1) and only the **uncached suffix**
is prefetched into fresh pages and prefilled — hit-aware admission needs
free pages for suffix + budget only.  Full prompt pages are registered
after reservation (so queue-mates in the same refill round already hit),
and retirement parks registered pages in the evictable cached state
instead of freeing them — reclaimed LRU/leaf-first on pool pressure, so
the cache reserves zero capacity.  Attention-only: hybrid SSM models are
rejected (a recurrent state cannot resume from a cached page).

Admission policy (``cfg.admission``): ``"fifo"`` (default) keeps strict
head-of-line order — if the head's pages don't fit, nothing joins until a
retirement frees them.  ``"skip-ahead"`` scans up to
``cfg.admission_lookahead`` queued requests for the first admissible one
when the head blocks: higher slot occupancy under mixed prompt sizes, at
the cost of a bounded reorder window (per-slot lengths keep every
request's tokens schedule-independent either way).  **Aging** bounds the
reordering: every time a blocked request is bypassed its skip count
grows, and once it reaches ``cfg.admission_max_skips`` it becomes a
barrier — the lookahead scan stops at it, so sustained small-request
load cannot starve a big prompt indefinitely (``max_skips=0``
degenerates skip-ahead to FIFO).

Chunked prefill (``cfg.prefill_chunk``, needs paged): a long prompt's
uncached suffix no longer monopolizes one join — it is prefilled in
page-aligned chunks of at most ``prefill_chunk`` tokens, one chunk per
refill round, the slot sitting in the **PREFILLING** state in between:

    queued --admit--> PREFILLING --last chunk--> decoding --EOS/budget-->
    retired            (chunks interleave with other slots' decode
                        segments; device done-latch keeps the slot
                        frozen — no sampling, no cache growth, PAD
                        emissions — while its table row keeps accepting
                        chunk scatters at ``cache_len`` = filled depth)

Pages for the whole worst case are still reserved at admission (no
mid-prefill preemption); each continuation round re-enters the same
``jit_paged_join`` with ``prefix_lens`` = the filled depth, exactly the
suffix-resume path the prefix cache introduced, and only the final chunk
samples a first token (``commit_mask``).  Chunk boundaries are
page-aligned, so a frozen slot's placeholder decode writes (overwritten
by the next chunk) can never land in a shared prefix page, and prompt
pages are registered in the radix tree *as chunks cover them* — a
queue-mate can match and gather a page in the same join that writes it
(the join prefills its rows shallowest cached prefix first, so the writer
runs in the reader's group or an earlier one, and per layer scatters
precede gathers), but never one the writer has not reached.

Decode-priority chunk budget (``cfg.prefill_round_tokens``): by default a
refill round takes one chunk from *every* PREFILLING slot plus the first
chunk of every new admission, so many concurrent long prompts can still
make the round's join wide.  A round-token budget caps the total prefill
tokens a single round may take: once the running total reaches the cap,
further continuations are deferred to the next round (counted in
``join_stats()['budget_deferrals']``) and admission stops.  The first
piece of a round is always taken, so prefill always progresses — the
budget trades prefill throughput for decode latency explicitly.

Self-speculative decoding (``cfg.speculate_k``, needs paged; greedy and
attention-only): decode segments run the draft-k verify loop from
:func:`repro.serve.engine.make_decode_loop` — per step, k candidate
tokens are drafted from the slot's own prompt+output ``history`` (the
on-device n-gram/period lookup in ``engine.ngram_propose``) and verified
in one Lq = k+1 paged attention call; the per-slot accepted length
commits 1..k+1 tokens per step at bit-identical greedy output.  The
scheduler's part of the contract:

* **admission reserves the speculation window** — every verify writes
  K/V up to position ``lengths + k``, so the worst-case page reservation
  (and ``can_admit``, and the up-front ``max_len`` validation) grows
  from ``prompt + max_new`` to ``prompt + max_new + k`` tokens;
* **host history**: the prompt is written into the slot's history row at
  admission and the first sampled token at commit; during decode the
  device updates history inside the scan and the host mirror is synced
  back at each segment boundary (joins are host-sync points already);
* **variable advance**: ``emitted`` is [steps, B, k+1] — ``_collect``
  walks each step's committed burst (PAD-terminated) with the same
  EOS/budget retirement rules, and the per-step committed counts feed
  ``spec_stats()`` (acceptance rate = accepted drafts / proposed).

Optimistic admission + page-level preemption
(``cfg.admission_mode="optimistic"``, needs paged; attention-only):
reservation admission maps the full worst case (prompt + max_new + k) at
join time, so the pool runs far under its true capacity whenever outputs
finish early — ``kv_util_mean`` is the gap.  Optimistic admission maps
only the *prompt's* pages at join time and grows each decoding slot's
table on demand between segments (``_ensure_decode_pages``: cover the
segment's worst-case advance, ``steps * (k+1)`` tokens, capped by the
slot's total budget).  When growth outruns the pool, the scheduler picks
a **victim** under a deterministic policy — lowest priority class
(``submit(..., priority=)``), then most pages mapped, then least decode
progress, then lowest slot id — releases the victim's pages (dead
private pages park in the pool's *preempted* partition, registered
prefix pages stay evictable-cached) and re-queues it at the queue head:

    ... -> DECODING --pool pressure--> PREEMPTED (off device, pages
    released, host history keeps prompt + committed tokens) --re-admit-->
    PREFILLING/DECODING (recompute KV from history via the ordinary
    chunked-prefill join at absolute depth) --> ... -> retired

Resume is recompute-on-resume: the re-queued "prompt" is the original
prompt plus every committed token, so the ordinary suffix-prefill path
rebuilds the KV bit-exactly and the join's first sampled token is the
next token the uninterrupted run would have produced (greedy parity).
Pages the victim had covered are registered in the radix tree at
preemption (generated-token pages are immutable full pages too), so with
the prefix cache on the resume usually *matches* most of its history and
recomputes only a page-aligned tail.  No-livelock: every preemption
charges the request's preempt count, and at ``admission_max_skips`` the
request becomes an admission **barrier** (the PR 4 aging mechanism) —
nothing joins past it, the pool drains toward it, and since the victim
policy always evicts the least-progressed slot last, some slot always
runs to retirement, so every preempted request eventually completes.

Chaos injection (``chaos=``, repro.serve.chaos): a deterministic
round-keyed injector can force pool exhaustion (``KVPool.hold`` on the
free list), override victim selection, simulate slot failure
mid-decode (handled as a preemption — recompute-on-resume *is* the
recovery path), suppress whole scheduling rounds (``stall_at``, the
watchdog drill) and inject synthetic queue bursts (``burst_at``), with
optional per-round ``KVPool.check()`` / ``PrefixCache.check()``
invariant sweeps.

Overload protection (repro.serve.overload): deadlines and cancellation
are always on — ``submit(deadline_s=..., timeout_s=...)`` stamps
per-request absolute deadlines, and a per-round sweep cancels requests
whose deadline/timeout passed or whose remaining-budget projection
(observed TTFT/TPOT means) can no longer meet the deadline.  CANCELLED
is a terminal lifecycle state (QUEUED→CANCELLED releases nothing;
PREFILLING/DECODING→CANCELLED releases pages through ``_release_slot``
and done-latches the device row exactly like a preemption, minus the
re-queue), traced as a ``CANCEL`` event with a reason code
(deadline / timeout / shed / client).  ``cfg.overload`` arms the
degradation controller (HEALTHY→DEGRADED→SHEDDING on SLO burn rate +
pool pressure): DEGRADED sheds speculation and shrinks the prefill
chunk, SHEDDING freezes optimistic growth (admission reverts to
worst-case reservation) and sheds lowest-priority queued work with a
retryable RETRY_AFTER rejection.  Degradation only changes when and
whether work runs — every request that completes stays bit-exact.  A
progress watchdog (``cfg.watchdog_rounds`` rounds with no join, commit,
retirement, preemption or cancellation) replaces the old idle-spin
guard: it dumps the flight-recorder bundle and force-sheds the blocking
head instead of raising, so a livelocked drain finishes (minus the shed
requests) and ships its own postmortem.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .engine import (PAD_TOKEN, ServeConfig, jit_decode_loop, jit_join,
                     jit_paged_decode_loop, jit_paged_join,
                     jit_spec_decode_loop, paged_join_rows)
from .kvpool import KVPool, PageError
from .overload import (CANCEL_REASONS, HEALTHY, RETRY_AFTER, STATES,
                       DegradationController, Watchdog, WatchdogStall,
                       project_finish_s)
from .prefixcache import PrefixCache
# _pct moved to telemetry (the registry owns percentile math) but stays
# importable from here — it has always been this module's public helper
from .telemetry import MetricsRegistry, Tracer, _pct, phase  # noqa: F401
from ..kernels.paged_attn.ops import decode_launch
from ..models.model_zoo import Model


def _pow2_bucket(n: int, lo: int = 16, hi: int | None = None) -> int:
    b = max(lo, 1 << max(0, n - 1).bit_length())
    return min(b, hi) if hi is not None else b


class ContinuousBatcher:
    """Greedy continuous batcher over a fixed slot table (see module doc).

    Drop-in upgrade of the seed per-token ``Batcher``: same
    ``submit``/``run`` surface, but the hot path is a jitted ``lax.scan``
    with donated caches, device-side sampling and per-slot lengths instead
    of a per-token Python loop with host argmax.
    """

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 eos_id: int | None = None, seed: int = 0, chaos=None,
                 telemetry: Tracer | None = None):
        self.model, self.params, self.cfg = model, params, cfg
        self.eos = eos_id
        # every accumulated stat lives in the registry (the *_stats()
        # methods and the legacy counter attributes are views over it);
        # the tracer is optional — None (the default, unless
        # cfg.telemetry asks for one) keeps every event call site a
        # skipped ``if`` at scheduling-round boundaries
        self.metrics = MetricsRegistry()
        self.telemetry = (telemetry if telemetry is not None
                          else Tracer() if cfg.telemetry else None)
        # flight recorder: an always-on bounded ring of the same
        # lifecycle events (host dict appends only — no spans, no pool
        # gauge callback, no device syncs, so the traced==untraced
        # parity contract holds).  Dumped as a debug bundle when a
        # PageError escapes the run loop (see ``_dump_flight``).
        self.flight = (Tracer(ring=cfg.flight_events)
                       if cfg.flight_recorder else None)
        self.last_flight_bundle: dict | None = None
        # SLO accounting: priority classes that have scored at least one
        # sample (the met/total counters themselves live in the
        # registry, so ``reset_stats`` clears them with everything else)
        self._slo_classes: set[int] = set()
        self.queue: collections.deque[tuple[int, list[int]]] = \
            collections.deque()
        self.results: dict[int, list[int]] = {}
        if cfg.admission not in ("fifo", "skip-ahead"):
            raise ValueError(f"unknown admission policy {cfg.admission!r}")
        if cfg.admission_mode not in ("reserve", "optimistic"):
            raise ValueError(
                f"unknown admission mode {cfg.admission_mode!r} "
                "(expected 'reserve' or 'optimistic')")
        if cfg.admission_mode == "optimistic":
            from ..configs.base import BlockKind
            if not cfg.paged:
                raise ValueError(
                    "admission_mode='optimistic' requires paged=True "
                    "(on-demand growth and preemption move pages through "
                    "the pool)")
            if any(s.kind is BlockKind.SSM
                   for s in model.cfg.resolved_segments()):
                raise ValueError(
                    "optimistic admission is attention-only: preempting a "
                    "hybrid SSM slot would discard a recurrent state that "
                    "recompute-on-resume cannot rebuild from paged KV")
        self.chaos = chaos
        if chaos is not None and not cfg.paged:
            raise ValueError("chaos injection requires paged=True (its "
                             "faults move pages through the pool)")
        if cfg.prefill_chunk is not None:
            from ..configs.base import BlockKind
            if not cfg.paged:
                raise ValueError("prefill_chunk requires paged=True "
                                 "(chunks resume through the page table)")
            if cfg.prefill_chunk <= 0:
                raise ValueError("prefill_chunk must be positive")
            if cfg.prefill_chunk % cfg.page_size:
                raise ValueError(
                    f"prefill_chunk {cfg.prefill_chunk} must be a multiple "
                    f"of page_size {cfg.page_size} (chunk boundaries must "
                    "never land inside a shared prefix page)")
            if any(s.kind is BlockKind.SSM
                   for s in model.cfg.resolved_segments()):
                raise ValueError(
                    "prefill_chunk is attention-only: a hybrid SSM "
                    "model's recurrent state cannot resume mid-prompt "
                    "across join calls")
        if cfg.prefill_round_tokens is not None \
                and cfg.prefill_round_tokens <= 0:
            raise ValueError("prefill_round_tokens must be positive")
        self.spec_k = cfg.speculate_k or 0
        if cfg.speculate_k is not None:
            from ..configs.base import BlockKind
            if not cfg.paged:
                raise ValueError(
                    "speculate_k requires paged=True (the verify step "
                    "writes and rolls back through the page table)")
            if cfg.speculate_k < 1:
                raise ValueError("speculate_k must be >= 1")
            if cfg.speculate_ngram < 1:
                raise ValueError("speculate_ngram must be >= 1")
            if cfg.temperature != 0.0:
                raise ValueError(
                    "speculate_k is greedy-only for now: acceptance is "
                    "defined by exact argmax agreement (temperature 0)")
            if any(s.kind is BlockKind.SSM
                   for s in model.cfg.resolved_segments()):
                raise ValueError(
                    "speculate_k is attention-only: a hybrid SSM model's "
                    "recurrent state advances k+1 tokens per verify and "
                    "cannot roll back past the acceptance point")
        b = cfg.batch
        if cfg.paged:
            self.pool = KVPool(cfg.pool_pages, cfg.page_size, b,
                               max_pages=cfg.max_pages)
            self.caches = model.init_paged_caches(
                b, cfg.pool_pages, cfg.page_size, cfg.dtype)
            self._join = jit_paged_join(model, cfg, eos_id=eos_id)
        else:
            self.pool = None
            self.caches = model.init_caches(b, cfg.max_len, cfg.dtype)
            self._join = jit_join(model, cfg, eos_id=eos_id)
        self.prefix: PrefixCache | None = None
        if cfg.prefix_cache:
            from ..configs.base import BlockKind
            if not cfg.paged:
                raise ValueError("prefix_cache requires paged=True "
                                 "(shared pages live in the block pool)")
            if any(s.kind is BlockKind.SSM
                   for s in model.cfg.resolved_segments()):
                raise ValueError(
                    "prefix_cache is attention-only: hybrid SSM models "
                    "cannot resume a recurrent state from cached pages")
            self.prefix = PrefixCache(self.pool)
        if self.telemetry is not None and self.pool is not None:
            # pool-partition gauge: every allocator mutation lands one
            # counter sample in the trace (and the current-state gauges)
            self.pool.gauge_cb = self._on_pool_gauge
        self.tok = jnp.zeros((b, 1), jnp.int32)
        self.lengths = jnp.zeros((b,), jnp.int32)
        self.done = jnp.ones((b,), bool)
        self.remaining = jnp.zeros((b,), jnp.int32)
        self.key = jax.random.key(seed)
        # host mirror of the slot table
        self.slot_rid: list[int | None] = [None] * b
        self.slot_len = [0] * b
        self.slot_budget = [0] * b
        # chunked-prefill state: a slot with pending suffix tokens is
        # PREFILLING (device done-latch frozen); ``slot_filled`` mirrors
        # the device ``lengths`` row = prompt tokens resident so far
        self.slot_pending: list[list[int]] = [[] for _ in range(b)]
        self.slot_prompt: list[list[int] | None] = [None] * b
        self.slot_filled = [0] * b
        self.outputs: dict[int, list[int]] = {}
        self._loops: dict[tuple[int, int | None], object] = {}
        # KV memory accounting, sampled once per decode segment:
        # (live tokens, allocated token capacity, live slots)
        self.kv_samples: list[tuple[int, int, int]] = []
        # skip-ahead aging: times each queued rid has been bypassed
        self._skips: dict[int, int] = {}
        self.admit_order: list[int] = []
        # self-speculation: host mirror of the per-slot token history the
        # device drafter reads (prompt at admission, first token at
        # commit, then synced back from the scan carry each segment)
        self.history = np.zeros((b, cfg.max_len), np.int32)
        # request latency trajectory: wall-clock TTFT (run start -> first
        # sampled token) and time-per-output-token per retired request —
        # the samples themselves live in the registry ("lat.*" hists)
        self._clock0: float | None = None
        self._first_tok_t: dict[int, float] = {}
        # queue-wait trajectory: submit (or preemption) -> admission
        self._submit_t: dict[int, float] = {}
        # optimistic admission / preemption state: per-request priority
        # class (victim policy evicts lowest first), the slot's total
        # token ceiling (prompt + remaining budget + spec window — what
        # on-demand growth may cover), how many committed tokens predate
        # the slot's current admission (a re-preempted slot's resume
        # prompt is slot_prompt + outputs[slot_prior:]), and the rids
        # currently living between preemption and retirement
        self.req_priority: dict[int, int] = {}
        self.slot_max_tokens = [0] * b
        self.slot_prior = [0] * b
        self._resumed: set[int] = set()
        self._preempt_counts: dict[int, int] = {}
        self.preempted_rids: set[int] = set()
        self.preempt_events: list[tuple[int, int, int, str]] = []
        # overload protection: per-request absolute deadline/timeout
        # stamps, terminal cancellations (rid -> reason code), the
        # RETRY_AFTER rejections shed queued work was answered with, the
        # opt-in degradation controller and the always-on progress
        # watchdog (which replaces the old 100k-idle-round guard)
        self._deadline_t: dict[int, float] = {}
        self._timeout_t: dict[int, float] = {}
        self.cancelled: dict[int, str] = {}
        self.rejections: list[dict] = []
        if cfg.watchdog_rounds < 1:
            raise ValueError("watchdog_rounds must be >= 1")
        self.overload = (DegradationController(
            degrade_burn=cfg.overload_degrade_burn,
            shed_burn=cfg.overload_shed_burn,
            degrade_pressure=cfg.overload_degrade_pressure,
            shed_pressure=cfg.overload_shed_pressure,
            up_rounds=cfg.overload_up_rounds,
            down_rounds=cfg.overload_down_rounds)
            if cfg.overload else None)
        self.watchdog = Watchdog(cfg.watchdog_rounds)
        # chaos ``stall_at``: rounds below this bound skip the whole
        # round body (the deterministic livelock the watchdog drills on)
        self._stall_until = 0
        self._max_new = 0
        # keep the host history mirror warm whenever speculation is
        # *configured*, even while the controller has shed it — a
        # re-enabled drafter must read a corpus that covers the tokens
        # plain decode committed in between (wrong drafts only cost
        # acceptance, but a warm mirror keeps them right)
        self._hist_on = cfg.speculate_k is not None
        # scheduling-round counter: the chaos injector keys on it
        self.round = 0

    # ------------------------------------------------------------------
    # legacy counter surface: every accumulated stat is stored in the
    # metrics registry; these read-only views keep the attribute names
    # tests, benches and older callers read (no churn, one store)
    # ------------------------------------------------------------------
    @property
    def prefill_computed(self) -> int:
        return int(self.metrics.value("prefill.computed_tokens"))

    @property
    def prefill_skipped(self) -> int:
        return int(self.metrics.value("prefill.skipped_tokens"))

    @property
    def prefix_admits(self) -> int:
        return int(self.metrics.value("prefix.admits"))

    @property
    def prefix_hits(self) -> int:
        return int(self.metrics.value("prefix.hits"))

    @property
    def chunk_joins(self) -> int:
        return int(self.metrics.value("join.chunk_continuations"))

    @property
    def budget_deferrals(self) -> int:
        return int(self.metrics.value("join.budget_deferrals"))

    @property
    def spec_steps(self) -> int:
        return int(self.metrics.value("spec.steps"))

    @property
    def spec_proposed(self) -> int:
        return int(self.metrics.value("spec.proposed"))

    @property
    def spec_accepted(self) -> int:
        return int(self.metrics.value("spec.accepted"))

    @property
    def spec_emitted(self) -> int:
        return int(self.metrics.value("spec.emitted"))

    @property
    def preemptions(self) -> int:
        return int(self.metrics.value("preempt.count"))

    @property
    def preempted_token_recompute(self) -> int:
        return int(self.metrics.value("preempt.recompute_tokens"))

    @property
    def join_times(self) -> list[float]:
        return self.metrics.samples("join.seconds")

    @property
    def ttfts(self) -> list[float]:
        return self.metrics.samples("lat.ttft_s")

    @property
    def tpots(self) -> list[float]:
        return self.metrics.samples("lat.tpot_s")

    @property
    def queue_waits(self) -> list[float]:
        return self.metrics.samples("lat.queue_wait_s")

    # ------------------------------------------------------------------
    # telemetry plumbing (every call site guards on ``telemetry is None``
    # — tracing off is the default and costs one attribute test per
    # scheduling-round boundary, nothing on jitted paths)
    # ------------------------------------------------------------------
    def _on_pool_gauge(self, **counts) -> None:
        tr = self.telemetry
        if tr is not None:
            tr.pool_gauge(counts)
        for k, v in counts.items():
            self.metrics.set_gauge(f"pool.{k}_pages", v)

    def _trace(self, kind: str, rid: int | None,
               slot: int | None = None, **attrs) -> None:
        tr, fl = self.telemetry, self.flight
        if tr is None and fl is None:
            return
        pages = (len(self.pool.slot_pages(slot))
                 if self.pool is not None and slot is not None else 0)
        free = self.pool.free_pages if self.pool is not None else 0
        pages_held = attrs.pop("pages_held", pages)
        pool_free = attrs.pop("pool_free", free)
        if tr is not None:
            tr.event(kind, rid, round=self.round, slot=slot,
                     pages_held=pages_held, pool_free=pool_free, **attrs)
        if fl is not None:
            fl.event(kind, rid, round=self.round, slot=slot,
                     pages_held=pages_held, pool_free=pool_free, **attrs)

    def _slo_observe(self, metric: str, rid: int, v: float) -> None:
        """Score one observed latency against its configured SLO, per
        priority class.  No-op (beyond the attribute test) when the SLO
        for that metric is unset."""
        slo = (self.cfg.ttft_slo_s if metric == "ttft"
               else self.cfg.tpot_slo_s)
        if slo is None:
            return
        cls = self.req_priority.get(rid, 0)
        self._slo_classes.add(cls)
        self.metrics.inc(f"slo.{metric}_total.c{cls}")
        if v <= slo:
            self.metrics.inc(f"slo.{metric}_met.c{cls}")

    # ------------------------------------------------------------------
    def submit(self, rid: int, prompt: list[int], priority: int = 0,
               deadline_s: float | None = None,
               timeout_s: float | None = None) -> None:
        """Queue a request.  ``priority`` is its SLO class for the
        preemption victim policy — higher values are evicted later
        (ties fall back to most-pages / least-progress).

        ``deadline_s`` is the client's completion deadline, seconds from
        now: the per-round sweep cancels the request (reason
        ``"deadline"``) once the deadline passes *or* once the
        remaining-budget TTFT/TPOT projection says it can no longer be
        met — pages come back immediately instead of at the doomed
        completion.  ``timeout_s`` is a hard lifetime cap (reason
        ``"timeout"``): no projection, only actual expiry.  Deadline
        attainment (``latency_stats()['deadline_attainment']``) scores
        deadline-carrying requests that completed or expired; shed /
        client cancels are excluded (a RETRY_AFTER rejection is a fast
        failure, not a latency violation)."""
        if not prompt:
            raise ValueError("empty prompt")
        now = time.perf_counter()
        if deadline_s is not None:
            if deadline_s < 0:
                raise ValueError("deadline_s must be >= 0")
            self._deadline_t[rid] = now + deadline_s
        if timeout_s is not None:
            if timeout_s < 0:
                raise ValueError("timeout_s must be >= 0")
            self._timeout_t[rid] = now + timeout_s
        self.queue.append((rid, list(prompt)))
        self.req_priority[rid] = priority
        self._submit_t[rid] = now
        self._trace("SUBMIT", rid, prompt_tokens=len(prompt),
                    priority=priority, deadline_s=deadline_s,
                    timeout_s=timeout_s)

    # ------------------------------------------------------------------
    def _spec_live(self) -> int:
        """The speculation window actually in force this round: the
        configured ``spec_k`` unless the degradation controller has shed
        speculation (DEGRADED+).  Shedding is loss-free for tokens —
        speculative and plain greedy decode are bit-identical — it only
        trades the steps-per-token win for smaller verify writes and
        smaller on-demand page growth."""
        if self.overload is not None and self.overload.shed_speculation:
            return 0
        return self.spec_k

    def _effective_chunk(self) -> int | None:
        """The prefill chunk in force this round: halved (page-aligned,
        floored at one page) while the controller is DEGRADED+ — shorter
        joins stall live slots' decode for less at the cost of more
        continuation rounds.  Unchunked configs stay unchunked (the
        controller never *introduces* a feature)."""
        chunk = self.cfg.prefill_chunk
        if (chunk is not None and self.overload is not None
                and self.overload.shrink_chunk):
            ps = self.cfg.page_size
            return max(ps, (chunk // 2) // ps * ps)
        return chunk

    def _loop(self, steps: int, cap: int | None):
        # the spec flag keys the cache too: the controller can shed
        # speculation mid-run, and the spec/plain loops take different
        # carries — a (steps, cap) collision across modes would replay
        # the wrong executable
        keyid = (steps, cap, bool(self._spec_live()))
        if keyid not in self._loops:
            if self._spec_live():
                self._loops[keyid] = jit_spec_decode_loop(
                    self.model, self.cfg, steps=steps, eos_id=self.eos)
            elif self.cfg.paged:
                # cap shapes the page-table slice; the jit keys on it
                self._loops[keyid] = jit_paged_decode_loop(
                    self.model, self.cfg, steps=steps, eos_id=self.eos)
            else:
                self._loops[keyid] = jit_decode_loop(
                    self.model, self.cfg, steps=steps, eos_id=self.eos,
                    kv_cap=cap)
        return self._loops[keyid]

    def _kv_cap(self, steps: int) -> int | None:
        live = [self.slot_len[i] for i, r in enumerate(self.slot_rid)
                if r is not None]
        if not live:
            return None
        cap = _pow2_bucket(max(live) + steps, hi=self.cfg.max_len)
        return None if cap >= self.cfg.max_len else cap

    def _page_cap(self) -> int:
        """Power-of-two bound on the deepest live slot's *allocated* page
        count (allocation covers prompt + budget, so a segment can never
        outgrow it) — the paged analogue of ``_kv_cap``."""
        live = [len(self.pool.slot_pages(i))
                for i, r in enumerate(self.slot_rid) if r is not None]
        if not live:
            return self.cfg.max_pages
        return _pow2_bucket(max(live), lo=2, hi=self.cfg.max_pages)

    def _note_admitted(self, rid: int) -> None:
        """Close the request's queue-wait interval (opened at submit and
        re-opened at each preemption)."""
        t0 = self._submit_t.pop(rid, None)
        if t0 is not None:
            self.metrics.observe("lat.queue_wait_s",
                                 time.perf_counter() - t0)

    # ------------------------------------------------------------------
    def _admit_next(self, slot: int, max_new: int):
        """Pop and reserve the next admissible queued request for ``slot``.

        Paged admission matches the prompt against the prefix cache first:
        matched pages are mapped via ``KVPool.share`` and only suffix +
        budget pages must be free (hit-aware admission).  FIFO blocks on
        the queue head; ``skip-ahead`` scans a bounded lookahead window
        for the first request whose pages fit — charging every bypassed
        request one skip, and never scanning past a request whose skip
        count has aged to ``cfg.admission_max_skips`` (the starvation
        bound).  Returns ``(rid, prompt, matched_tokens)`` or None.
        """
        if not self.queue:
            return None
        if self.pool is None:
            rid, p = self.queue.popleft()
            self.admit_order.append(rid)
            self._note_admitted(rid)
            self._trace("ADMIT", rid, slot=slot, prompt_tokens=len(p))
            return rid, p, 0
        # SHEDDING freezes optimistic slot growth at the source: new
        # admissions revert to worst-case reservation, so they can never
        # demand on-demand pages (and thus preemptions) later — already
        # live optimistic slots still grow as needed (they must, or
        # their verify writes would land outside their tables)
        optimistic = (self.cfg.admission_mode == "optimistic"
                      and not (self.overload is not None
                               and self.overload.freeze_growth))
        window = 1
        if self.cfg.admission == "skip-ahead":
            window = min(len(self.queue), self.cfg.admission_lookahead)
        for qi in range(window):
            rid, p = self.queue[qi]
            # a resume's "prompt" already contains ``prior`` committed
            # tokens, so only the *remaining* budget counts toward its
            # worst case — the total never exceeds the original admission
            prior = (len(self.outputs.get(rid, ()))
                     if rid in self._resumed else 0)
            ceiling = len(p) + (max_new - prior) + self.spec_k
            matched: list[int] = []
            mtoks = 0
            if self.prefix is not None:
                matched, mtoks = self.prefix.match(p)
            # reserve mode admits the worst case up front (the spec
            # window counts: a verify at the budget edge writes K/V up
            # to lengths + spec_k); optimistic mode admits on the
            # prompt's pages only and grows on demand between segments
            admit_tokens = len(p) if optimistic else ceiling
            if not self.pool.can_admit(admit_tokens,
                                       shared_pages=matched):
                if self._skips.get(rid, 0) >= self.cfg.admission_max_skips:
                    # aged out: this blocked request is now a barrier —
                    # nothing may be admitted past it until it fits
                    break
                continue
            del self.queue[qi]
            for prev in range(qi):
                # everything scanned past was blocked: charge one skip
                self._skips[self.queue[prev][0]] = \
                    self._skips.get(self.queue[prev][0], 0) + 1
            self._skips.pop(rid, None)
            self.admit_order.append(rid)
            self._note_admitted(rid)
            self.slot_max_tokens[slot] = ceiling
            total = self.pool.pages_for(admit_tokens)
            if matched:
                # refcounts go above 1 here: the prefix chain is mapped
                # into this slot's table on top of its other references
                self.pool.share(slot, matched)
                if total > len(matched):
                    self.pool.extend(slot, total - len(matched))
            else:
                self.pool.reserve(slot, admit_tokens)
            if self.prefix is not None:
                # register the pages the *first chunk* will have written
                # by the end of this refill round's join, so queue-mates
                # in the same round already match them; later chunks
                # extend the registration as they cover more pages
                # (unchunked: the first chunk is the whole prompt)
                chunk = self._effective_chunk()
                covered = (len(p) if chunk is None
                           else min(len(p), mtoks + chunk))
                self._register_covered(slot, p, covered)
                self.metrics.inc("prefix.admits")
                self.metrics.inc("prefix.hits", int(bool(mtoks)))
            self._trace("ADMIT", rid, slot=slot, prompt_tokens=len(p),
                        matched_tokens=mtoks)
            if rid in self._resumed:
                # recompute-on-resume re-enters through ordinary
                # admission — the RESUME mark pairs with its PREEMPT
                self._trace("RESUME", rid, slot=slot,
                            prior_tokens=len(self.outputs.get(rid, ())))
            return rid, p, mtoks
        return None

    def _register_covered(self, slot: int, prompt: list[int],
                          covered: int) -> None:
        """Insert ``prompt``'s full pages up to ``covered`` resident
        tokens into the radix tree (idempotent for already-registered
        chunks — continuation rounds extend the chain)."""
        ps = self.pool.page_size
        n_full = min(covered, len(prompt)) // ps
        if n_full:
            self.prefix.insert(prompt[:n_full * ps],
                               self.pool.slot_pages(slot)[:n_full])

    def _release_slot(self, slot: int) -> None:
        """Return ``slot``'s pages; registered prefix pages whose refcount
        hits zero park in the evictable cached state, everything else goes
        straight back to the free list."""
        if self.pool is None:
            return
        cacheable = frozenset()
        if self.prefix is not None:
            cacheable = self.prefix.registered_pages(
                self.pool.slot_pages(slot))
        self.pool.release(slot, cacheable=cacheable)
        self.slot_pending[slot] = []
        self.slot_prompt[slot] = None
        self.slot_filled[slot] = 0
        self.slot_prior[slot] = 0
        self.slot_max_tokens[slot] = 0

    # ------------------------------------------------------------------
    # page-level preemption (optimistic admission / chaos slot failure)
    # ------------------------------------------------------------------
    def _preempt_slot(self, slot: int, reason: str = "pressure") -> None:
        """Evict a live slot at a segment boundary: register its covered
        pages in the radix tree (so the resume can shortcut recompute),
        release its pages (unregistered ones park in the pool's preempted
        partition), latch its device row done, and re-queue the request
        at the queue head with prompt = everything committed so far —
        the ordinary chunked-prefill path then recomputes the KV
        bit-exactly (recompute-on-resume)."""
        rid = self.slot_rid[slot]
        if rid is None:
            raise RuntimeError(f"preempt of empty slot {slot}")
        prompt = self.slot_prompt[slot]
        if self.slot_pending[slot]:
            # PREFILLING: no tokens committed under *this* admission yet;
            # the resume replays the same (resume-)prompt from the top
            resident = self.slot_filled[slot]
            resume = list(prompt)
            known = prompt[:resident]
        else:
            # DECODING: resume prompt = this admission's prompt plus the
            # tokens committed since (``slot_prior`` marks the split, so
            # a second preemption never duplicates older outputs).  The
            # last committed token has no KV yet (it is the *input* of
            # the next step), hence ``known`` stops one short.
            out = self.outputs[rid]
            resident = self.slot_len[slot]
            resume = list(prompt) + out[self.slot_prior[slot]:]
            known = resume[:-1]
        if self.prefix is not None and resident:
            # generated-token pages are immutable full pages of real KV:
            # registering them lets the resume *match* its own history
            # and recompute only the page-aligned tail
            self._register_covered(slot, known, resident)
        cacheable = frozenset()
        if self.prefix is not None:
            cacheable = self.prefix.registered_pages(
                self.pool.slot_pages(slot))
        pages_released = len(self.pool.slot_pages(slot))
        self.pool.release(slot, cacheable=cacheable, preempt=True)
        self.slot_rid[slot] = None
        self.slot_pending[slot] = []
        self.slot_prompt[slot] = None
        self.slot_filled[slot] = 0
        self.slot_len[slot] = 0
        self.slot_prior[slot] = 0
        self.slot_max_tokens[slot] = 0
        # freeze the abandoned device row: done-latched rows stop
        # sampling and growing their cache, and their table row is the
        # OOB sentinel after release, so any residual write drops
        self.done = self.done.at[slot].set(True)
        self.remaining = self.remaining.at[slot].set(0)
        self.queue.appendleft((rid, resume))
        self._resumed.add(rid)
        self.preempted_rids.add(rid)
        self.metrics.inc("preempt.count")
        self._trace("PREEMPT", rid, slot=slot, reason=reason,
                    pages_held=pages_released, resident_tokens=resident)
        self._submit_t[rid] = time.perf_counter()   # re-open queue wait
        n = self._preempt_counts[rid] = self._preempt_counts.get(rid, 0) + 1
        if n >= max(1, self.cfg.admission_max_skips):
            # thrash bound: an often-preempted request becomes an
            # admission barrier (the skip-ahead aging mechanism) and the
            # victim policy marks it protected — the pool drains toward
            # it, so it cannot be starved by re-admissions
            self._skips[rid] = self.cfg.admission_max_skips
        self.preempt_events.append((self.round, rid, slot, reason))

    def _pick_victim(self, requester: int | None = None) -> int | None:
        """Deterministic victim policy over live slots: barrier-protected
        last, then lowest priority class, most pages mapped, least decode
        progress, lowest slot id.  A chaos override (if armed) replaces
        the policy for this one decision."""
        cands = [i for i, r in enumerate(self.slot_rid) if r is not None]
        if not cands:
            return None
        if self.chaos is not None:
            v = self.chaos.pick_victim(self, list(cands))
            if v is not None:
                return v
        max_skips = max(1, self.cfg.admission_max_skips)

        def key(i: int):
            rid = self.slot_rid[i]
            protected = self._preempt_counts.get(rid, 0) >= max_skips
            progress = (0 if self.slot_pending[i]
                        else len(self.outputs.get(rid, ())))
            return (protected, self.req_priority.get(rid, 0),
                    -len(self.pool.slot_pages(i)), progress, i)
        return min(cands, key=key)

    def _ensure_decode_pages(self, steps: int) -> None:
        """Optimistic mode: before a decode segment, grow every decoding
        slot's page table to cover the segment's worst-case advance
        (``steps * (spec_k + 1)`` tokens, capped by the slot's total
        budget), preempting victims when the pool cannot cover it.
        Highest-priority slots grow first, so pressure evicts in policy
        order; a slot picked as its own victim simply stops (its demand
        left with it)."""
        if self.pool is None or self.cfg.admission_mode != "optimistic":
            return
        adv = steps * (self._spec_live() + 1)
        order = sorted(
            (i for i, r in enumerate(self.slot_rid)
             if r is not None and not self.slot_pending[i]),
            key=lambda i: (-self.req_priority.get(self.slot_rid[i], 0), i))
        for slot in order:
            if self.slot_rid[slot] is None:
                continue                  # evicted by an earlier grow
            cover = min(self.slot_len[slot] + adv,
                        self.slot_max_tokens[slot])
            need = (self.pool.pages_for(cover)
                    - len(self.pool.slot_pages(slot)))
            if need <= 0:
                continue
            while need > (self.pool.free_pages + self.pool.preempted_pages
                          + self.pool.cached_pages):
                victim = self._pick_victim(requester=slot)
                if victim is None:
                    raise PageError(
                        f"cannot grow slot {slot} by {need} pages: no "
                        "live victim left and the pool cannot cover it")
                self._preempt_slot(victim, reason="pressure")
                if victim == slot:
                    break
            if self.slot_rid[slot] is not None:
                self.pool.extend(slot, need)

    # ------------------------------------------------------------------
    # cancellation: the terminal CANCELLED lifecycle state
    # (QUEUED→CANCELLED and PREFILLING/DECODING→CANCELLED)
    # ------------------------------------------------------------------
    def cancel(self, rid: int, reason: str = "client") -> bool:
        """Cancel a queued or in-flight request.  Mid-flight
        cancellation releases the slot's pages through the ordinary
        ``_release_slot`` path (registered prefix pages park
        evictable-cached — the KV is real and immutable, a later match
        may still use it) and done-latches the device row like a
        preemption, minus the re-queue.  Returns False when the rid is
        not queued or live (already retired or cancelled)."""
        if reason not in CANCEL_REASONS:
            raise ValueError(f"unknown cancel reason {reason!r} "
                             f"(expected one of {CANCEL_REASONS})")
        for qi, (qrid, _) in enumerate(self.queue):
            if qrid == rid:
                del self.queue[qi]
                self._finish_cancel(rid, None, reason)
                return True
        for slot, srid in enumerate(self.slot_rid):
            if srid == rid:
                self._cancel_slot(slot, reason)
                return True
        return False

    def _cancel_slot(self, slot: int, reason: str) -> None:
        rid = self.slot_rid[slot]
        if rid is None:
            raise RuntimeError(f"cancel of empty slot {slot}")
        pages = (len(self.pool.slot_pages(slot))
                 if self.pool is not None else 0)
        if self.prefix is not None and not self.slot_pending[slot]:
            # like a preemption: full pages of committed KV are real and
            # immutable — register them so the cache keeps the benefit
            # of the work the cancelled request already paid for
            out = self.outputs.get(rid, [])
            resume = (list(self.slot_prompt[slot])
                      + out[self.slot_prior[slot]:])
            self._register_covered(slot, resume[:-1] if out else resume,
                                   self.slot_len[slot])
        self._release_slot(slot)
        self.slot_rid[slot] = None
        self.slot_len[slot] = 0
        self.slot_budget[slot] = 0
        # freeze the abandoned device row (same contract as preemption):
        # done-latched rows stop sampling and growing their cache, and
        # the released table row is the OOB sentinel, so residual
        # writes drop
        self.done = self.done.at[slot].set(True)
        self.remaining = self.remaining.at[slot].set(0)
        self._finish_cancel(rid, slot, reason, pages_released=pages)

    def _finish_cancel(self, rid: int, slot: int | None, reason: str,
                       pages_released: int = 0) -> None:
        """Terminal bookkeeping shared by queued and mid-flight
        cancellation: reason ledger, counters, deadline-attainment
        accounting, RETRY_AFTER rejection for sheds, CANCEL trace."""
        self.cancelled[rid] = reason
        self._resumed.discard(rid)
        self._preempt_counts.pop(rid, None)
        self._skips.pop(rid, None)
        self._submit_t.pop(rid, None)
        dl = self._deadline_t.pop(rid, None)
        self._timeout_t.pop(rid, None)
        self.metrics.inc("cancel.count")
        self.metrics.inc(f"cancel.{reason}")
        if dl is not None and reason in ("deadline", "timeout"):
            # an expiry/projection cancel is a scored deadline miss;
            # shed/client cancels leave attainment untouched (the
            # request was answered, not served late)
            self.metrics.inc("deadline.total")
        attrs: dict = {}
        if reason == "shed":
            ra = self.cfg.overload_retry_after_s
            self.rejections.append({"rid": rid, "status": RETRY_AFTER,
                                    "retry_after_s": ra,
                                    "round": self.round})
            attrs["retry_after_s"] = ra
        self._trace("CANCEL", rid, slot=slot, reason=reason,
                    emitted_tokens=len(self.outputs.get(rid, ())),
                    pages_held=pages_released, **attrs)

    def _note_deadline_done(self, rid: int, now: float) -> None:
        """Score a retiring deadline-carrying request: met iff it
        completed at or before its absolute deadline."""
        dl = self._deadline_t.pop(rid, None)
        self._timeout_t.pop(rid, None)
        if dl is None:
            return
        self.metrics.inc("deadline.total")
        if now <= dl:
            self.metrics.inc("deadline.met")

    def _expired(self, rid: int, now: float) -> str | None:
        t = self._timeout_t.get(rid)
        if t is not None and now > t:
            return "timeout"
        d = self._deadline_t.get(rid)
        if d is not None and now > d:
            return "deadline"
        return None

    def _cancel_sweep(self, max_new: int) -> None:
        """Per-round deadline/timeout enforcement: cancel queued and
        live requests whose stamp expired, and deadline-carrying ones
        whose remaining-budget projection (observed TTFT/TPOT means —
        deliberately optimistic, see ``project_finish_s``) can no longer
        meet the deadline.  Runs with or without the degradation
        controller — deadlines are a request property, not a load
        policy."""
        if not self._deadline_t and not self._timeout_t:
            return
        now = time.perf_counter()
        for rid, _ in list(self.queue):
            reason = self._expired(rid, now)
            if reason is None and rid in self._deadline_t:
                prior = (len(self.outputs.get(rid, ()))
                         if rid in self._resumed else 0)
                proj = project_finish_s(self.metrics,
                                        max_new - prior, queued=True)
                if (proj is not None
                        and now + proj > self._deadline_t[rid]):
                    reason = "deadline"
            if reason is not None:
                self.cancel(rid, reason)
        for slot, rid in enumerate(self.slot_rid):
            if rid is None:
                continue
            reason = self._expired(rid, now)
            if (reason is None and rid in self._deadline_t
                    and not self.slot_pending[slot]):
                remaining = max(0, self.slot_budget[slot]
                                - len(self.outputs.get(rid, ())))
                proj = project_finish_s(self.metrics, remaining,
                                        queued=False)
                if (proj is not None
                        and now + proj > self._deadline_t[rid]):
                    reason = "deadline"
            if reason is not None:
                self._cancel_slot(slot, reason)

    # ------------------------------------------------------------------
    # degradation controller + progress watchdog (the observe→act loop)
    # ------------------------------------------------------------------
    def _overload_round(self) -> None:
        """Feed the controller this round's burn/pressure signals, trace
        any ladder transition, and apply the SHEDDING rung (queued-work
        shedding; the other rungs are consulted where the scheduler
        reads ``spec_k`` / ``prefill_chunk`` / admission sizing)."""
        ctl = self.overload
        slo = self.slo_stats()
        burn = max(slo["burn_rate_ttft"], slo["burn_rate_tpot"])
        pressure = self.pool.pressure() if self.pool is not None else 0.0
        prev = ctl.state
        state = ctl.observe(burn=burn, pressure=pressure,
                            queue_depth=len(self.queue),
                            round=self.round)
        if state != prev:
            self.metrics.inc("overload.transitions")
            self._trace("DEGRADE", None, state=state, prev=prev,
                        burn=round(burn, 4),
                        pressure=round(pressure, 4))
        if ctl.shedding:
            self._shed_queued()

    def _shed_queued(self) -> None:
        """SHEDDING's last rung: drain the queue down to
        ``overload_queue_keep`` (default: one slot-table's worth),
        lowest priority class first, latest-submitted first within a
        class, never a preempted resume (its work is already paid for —
        shedding it would waste the recompute and break the preemption
        liveness contract).  Every shed answers with a retryable
        RETRY_AFTER rejection."""
        keep = self.cfg.overload_queue_keep
        keep = self.cfg.batch if keep is None else keep
        while len(self.queue) > keep:
            cands = [(qi, rid) for qi, (rid, _) in enumerate(self.queue)
                     if rid not in self._resumed]
            if not cands:
                break
            qi, rid = min(cands, key=lambda c: (
                self.req_priority.get(c[1], 0), -c[0]))
            del self.queue[qi]
            self._finish_cancel(rid, None, "shed")

    def _progress_fingerprint(self) -> tuple:
        """Monotone progress counters the watchdog compares round over
        round: any join, committed token, retirement, preemption or
        cancellation moves at least one of them."""
        return (self.metrics.count("join.seconds"),
                int(self.metrics.value("preempt.count")),
                int(self.metrics.value("cancel.count")),
                len(self.results),
                sum(len(o) for o in self.outputs.values()))

    def _watchdog_tick(self) -> None:
        """Per-round progress check (replaces the idle-spin guard).  On
        a trip: dump the flight-recorder bundle (the postmortem the old
        RuntimeError never shipped), trace a WATCHDOG instant, and
        force-shed the blocking head — the run finishes minus the shed
        request instead of raising."""
        if not self.watchdog.tick(self._progress_fingerprint()):
            return
        live = sum(1 for r in self.slot_rid if r is not None)
        err = WatchdogStall(
            f"no scheduler progress for {self.cfg.watchdog_rounds} "
            f"rounds at round {self.round}: queue={len(self.queue)} "
            f"live_slots={live} (livelock/stall — shedding the "
            "blocking head instead of raising)")
        self._dump_flight(err)
        self.metrics.inc("watchdog.trips")
        self._trace("WATCHDOG", None,
                    stalled_rounds=self.cfg.watchdog_rounds,
                    queued=len(self.queue), live_slots=live)
        self._force_shed()

    def _force_shed(self) -> None:
        """Shed whatever is blocking the stalled drain: the queue head
        when work is queued (the request admission cannot place), else
        the lowest-priority live slot.  Barrier/priority protections do
        not apply — the alternative is the run never finishing."""
        if self.queue:
            rid, _ = self.queue.popleft()
            self._finish_cancel(rid, None, "shed")
            return
        live = [i for i, r in enumerate(self.slot_rid) if r is not None]
        if live:
            slot = min(live, key=lambda i: (
                self.req_priority.get(self.slot_rid[i], 0), i))
            self._cancel_slot(slot, "shed")

    # ------------------------------------------------------------------
    def _refill(self, max_new: int) -> None:
        """One round's prefill: the admission scan (PREFILLING slots'
        next chunks, then new admissions into free slots), then one join
        of every piece taken."""
        with phase("admit", self.telemetry, self.round):
            take = self._admit_pieces(max_new)
        if not take:
            return
        with phase("join", self.telemetry, self.round) as span:
            # the join prefills only each row's uncached suffix piece, so
            # the padded width (and the jit bucket) shrinks with hit depth
            # and is bounded by the chunk size
            width = _pow2_bucket(
                max(len(piece) for _, _, piece, _, _ in take),
                lo=8, hi=self.cfg.max_len)
            rows = (paged_join_rows(len(take)) if self.pool is not None
                    else self.cfg.batch)
            self.metrics.inc("join.rows_computed", rows)
            span.set_metadata(
                rows_computed=rows, width=width,
                tokens=sum(len(piece) for _, _, piece, _, _ in take))
            self._join_pieces(take, width, max_new)

    def _admit_pieces(self, max_new: int) -> list:
        """The pieces this round's join prefills, as (slot, rid, piece
        tokens, depth before this piece, commits?)."""
        chunk = self._effective_chunk()
        round_cap = self.cfg.prefill_round_tokens
        round_used = 0
        # (slot, rid, piece tokens, depth before this piece, commits?)
        take: list[tuple[int, int, list[int], int, bool]] = []
        # 1. PREFILLING slots first: their next chunk rides this join, and
        #    its about-to-be-covered pages are registered *before* the
        #    admission scan so queue-mates can match them (their KV is
        #    written by this very join, whose writer rows run no later
        #    than their readers: see make_paged_join)
        for slot, rid in enumerate(self.slot_rid):
            if rid is None or not self.slot_pending[slot]:
                continue
            if round_cap is not None and round_used >= round_cap:
                # decode-priority budget: this round already took its
                # prefill tokens — the continuation rides the next round
                self.metrics.inc("join.budget_deferrals")
                continue
            pend = self.slot_pending[slot]
            piece = pend[:chunk] if chunk else list(pend)
            depth = self.slot_filled[slot]
            if self.prefix is not None:
                self._register_covered(slot, self.slot_prompt[slot],
                                       depth + len(piece))
            take.append((slot, rid, piece, depth, len(piece) == len(pend)))
            self.metrics.inc("join.chunk_continuations")
            round_used += len(piece)
        # 2. new admissions into free slots (first chunk of each)
        free = [i for i, r in enumerate(self.slot_rid) if r is None]
        for fi, slot in enumerate(free):
            if not self.queue:
                break
            if round_cap is not None and round_used >= round_cap:
                # every remaining (free slot, queued request) pair is an
                # admission this budget pushed to a later round — count
                # them all so the metric matches the per-slot counting
                # of deferred continuations above
                self.metrics.inc("join.budget_deferrals",
                                 min(len(free) - fi, len(self.queue)))
                break
            cand = self._admit_next(slot, max_new)
            if cand is None:
                break
            rid, p, mtoks = cand
            suffix = p[mtoks:]
            piece = suffix[:chunk] if chunk else suffix
            self.slot_prompt[slot] = p
            self.slot_pending[slot] = suffix     # trimmed after the join
            take.append((slot, rid, piece, mtoks,
                         len(piece) == len(suffix)))
            round_used += len(piece)
            if self._hist_on:
                # the drafter's lookup corpus: the whole prompt is known
                # at admission (chunk continuations re-use this row) —
                # kept warm even while the controller sheds speculation,
                # so a re-enabled drafter reads a correct corpus
                self.history[slot, :len(p)] = p
        return take

    def _join_pieces(self, take: list, width: int, max_new: int) -> None:
        """Prefill ``take`` at ``width`` in one join call (full-batch
        arrays; the paged join computes only the rows in ``take``) and
        commit each completed prompt's first token."""
        t0 = time.perf_counter()
        b = self.cfg.batch
        self.metrics.observe("join.width", width)
        join_mask = np.zeros((b,), bool)
        commit_mask = np.zeros((b,), bool)
        prompts = np.zeros((b, width), np.int32)
        plens = np.ones((b,), np.int32)
        prefix_lens = np.zeros((b,), np.int32)
        budgets = np.full((b,), max_new, np.int32)
        for slot, rid, piece, depth, commit in take:
            join_mask[slot] = True
            commit_mask[slot] = commit
            prompts[slot, :len(piece)] = piece
            plens[slot] = len(piece)
            prefix_lens[slot] = depth
            self.metrics.inc("prefill.computed_tokens", len(piece))
            self._trace("PREFILL_CHUNK", rid, slot=slot,
                        tokens=len(piece), depth=depth, commit=commit,
                        recompute=rid in self._resumed)
            if rid in self._resumed:
                # prefill spent re-admitting a preempted request — the
                # direct cost of recompute-on-resume
                self.metrics.inc("preempt.recompute_tokens", len(piece))
                # the resume's device budget is only the *remaining*
                # tokens: its prompt already carries the committed ones,
                # so the done-latch must fire at the original total
                prior = len(self.outputs.get(rid, ()))
                if prior:
                    budgets[slot] = max(1, max_new - prior)
        join_args = (self.params, self.caches, self.tok, self.lengths,
                     self.done, self.remaining, jnp.asarray(join_mask),
                     jnp.asarray(prompts), jnp.asarray(plens),
                     jnp.asarray(budgets), self.key)
        if self.pool is not None:
            join_args += (jnp.asarray(self.pool.table),
                          jnp.asarray(prefix_lens),
                          jnp.asarray(commit_mask))
        (self.caches, self.tok, self.lengths, self.done, self.remaining,
         self.key, first) = self._join(*join_args)
        first = np.asarray(first)
        now = time.perf_counter()
        for slot, rid, piece, depth, commit in take:
            new_admission = self.slot_rid[slot] is None
            if new_admission:
                # cached-prefix tokens the join never had to compute
                self.metrics.inc("prefill.skipped_tokens", depth)
            self.slot_filled[slot] = depth + len(piece)
            self.slot_pending[slot] = self.slot_pending[slot][len(piece):]
            self.slot_len[slot] = self.slot_filled[slot]
            if not commit:
                self.slot_rid[slot] = rid         # PREFILLING: occupied,
                self.slot_budget[slot] = max_new  # frozen on device
                continue
            tokv = int(first[slot])
            prev = self.outputs.get(rid) if rid in self._resumed else None
            # ``slot_prior``: committed tokens that predate this
            # admission — a later preemption resumes from slot_prompt +
            # outputs[prior:], never duplicating older tokens
            self.slot_prior[slot] = len(prev) if prev is not None else 0
            if prev is not None:
                prev.append(tokv)                 # resume: keep history
                out = prev
            else:
                out = [tokv]
                self.outputs[rid] = out
            if self._clock0 is not None and rid not in self._first_tok_t:
                # a resumed request keeps its original first-token stamp
                self._first_tok_t[rid] = now
                self.metrics.observe("lat.ttft_s", now - self._clock0)
                self._slo_observe("ttft", rid, now - self._clock0)
                self._trace("FIRST_TOKEN", rid, slot=slot, token=tokv,
                            ttft_s=now - self._clock0)
            if self._hist_on:
                # newest token at position filled: the current token the
                # next verify step's tail n-gram ends on
                self.history[slot, self.slot_filled[slot]] = tokv
            if ((self.eos is not None and tokv == self.eos)
                    or len(out) >= max_new):
                self.results[rid] = out           # retired at commit
                self.slot_rid[slot] = None
                self._resumed.discard(rid)
                self._preempt_counts.pop(rid, None)
                self._note_deadline_done(rid, now)
                tpot = 0.0
                if (self._clock0 is not None and len(out) > 1
                        and rid in self._first_tok_t):
                    tpot = ((now - self._first_tok_t[rid])
                            / (len(out) - 1))
                self._trace("RETIRE", rid, slot=slot, tokens=len(out),
                            tpot_s=tpot)
                self._release_slot(slot)
                if tpot > 0.0:
                    self.metrics.observe("lat.tpot_s", tpot)
                    self._slo_observe("tpot", rid, tpot)
            else:
                self.slot_rid[slot] = rid
                self.slot_budget[slot] = max_new
        t1 = time.perf_counter()
        self.metrics.observe("join.seconds", t1 - t0)

    # ------------------------------------------------------------------
    def _collect(self, emitted: np.ndarray) -> None:
        """Drain one segment's emitted block into per-request outputs.

        Plain decode emits [steps, B] (one token per live step);
        speculative decode emits [steps, B, k+1] — each step is a
        PAD-terminated burst of 1..k+1 committed tokens whose length is
        that step's accepted advance.  A PAD ends the *step's* burst, not
        the slot: a live slot keeps committing in later steps, so only
        retirement (EOS/budget) stops the walk early.
        """
        if emitted.ndim == 2:
            emitted = emitted[:, :, None]
        steps, _, width = emitted.shape
        now = time.perf_counter()
        for i, rid in enumerate(self.slot_rid):
            if rid is None:
                continue
            if self.slot_pending[i]:
                # PREFILLING: the device row is done-latched and emits
                # only PADs until its last chunk commits — not a stall
                continue
            out = self.outputs[rid]
            appended = 0
            for t in range(steps):
                burst = 0
                for j in range(width):
                    v = int(emitted[t, i, j])
                    if v == PAD_TOKEN:
                        break
                    out.append(v)
                    burst += 1
                    appended += 1
                    self.slot_len[i] += 1
                    if (self._hist_on and width == 1
                            and self.slot_len[i] < self.cfg.max_len):
                        # plain-loop segment (speculation shed by the
                        # controller, or spec never carried): the device
                        # did not advance the history carry, so mirror
                        # the committed token here — same position
                        # convention as the spec loop (token at the
                        # post-advance length)
                        self.history[i, self.slot_len[i]] = v
                    if ((self.eos is not None and v == self.eos)
                            or len(out) >= self.slot_budget[i]):
                        self.results[rid] = out
                        self.slot_rid[i] = None
                        self._resumed.discard(rid)
                        self._preempt_counts.pop(rid, None)
                        self._note_deadline_done(rid, now)
                        tpot = 0.0
                        if (self._clock0 is not None and len(out) > 1
                                and rid in self._first_tok_t):
                            tpot = ((now - self._first_tok_t[rid])
                                    / (len(out) - 1))
                        self._trace("RETIRE", rid, slot=i,
                                    tokens=len(out), tpot_s=tpot)
                        # exact reclamation at this segment edge: private
                        # pages go back to the free list, registered
                        # prefix pages park evictable-cached for matches
                        self._release_slot(i)
                        if tpot > 0.0:
                            self.metrics.observe("lat.tpot_s", tpot)
                            self._slo_observe("tpot", rid, tpot)
                        break
                if self.spec_k and width > 1 and burst:
                    # one verify step committed ``burst`` tokens: burst-1
                    # drafts were accepted plus the model's bonus token
                    self.metrics.inc("spec.steps")
                    self.metrics.inc("spec.proposed", self.spec_k)
                    self.metrics.inc("spec.accepted", burst - 1)
                    self.metrics.inc("spec.emitted", burst)
                    self._trace("SPEC_COMMIT", rid, slot=i, step=t,
                                committed=burst,
                                accepted_drafts=burst - 1,
                                proposed=self.spec_k)
                if self.slot_rid[i] is None:
                    break
                if burst == 0:
                    # a live slot only emits an empty step once its
                    # device done-latch fired — every later step of this
                    # segment is PAD too (the stall check below still
                    # sees appended == 0 if the latch disagrees with
                    # host bookkeeping)
                    break
            if appended == 0 and self.slot_rid[i] is not None:
                raise RuntimeError(
                    f"slot {i} (request {rid}) stalled: device reports done "
                    "but host bookkeeping thinks it is live")

    # ------------------------------------------------------------------
    def run(self, max_new: int = 16) -> dict[int, list[int]]:
        """Drain the queue: refill slots, run fused decode segments, sync
        emitted tokens every ``cfg.sync_every`` steps."""
        if max_new <= 0:
            while self.queue:
                rid, _ = self.queue.popleft()
                self.results[rid] = []
            return self.results
        steps = max(1, self.cfg.sync_every)
        if self._clock0 is None:
            self._clock0 = time.perf_counter()
        # reject oversized requests up front, before anything is dequeued,
        # so a bad request never drops its queue-mates.  The speculation
        # window counts toward the worst case: a verify step writes K/V
        # (and needs table width) up to position lengths + spec_k.
        window = self.spec_k
        for rid, prompt in self.queue:
            if rid in self._resumed:
                # a resume's prompt carries committed tokens, so the
                # naive formula over-counts; it was validated (and its
                # total never grows) at its original admission
                continue
            if len(prompt) + max_new + window > self.cfg.max_len:
                raise ValueError(
                    f"request {rid}: prompt {len(prompt)} + max_new "
                    f"{max_new}"
                    + (f" + speculation window {window}" if window else "")
                    + f" exceeds max_len {self.cfg.max_len}")
            if (self.pool is not None
                    and self.pool.pages_for(len(prompt) + max_new + window)
                    > min(self.pool.n_pages, self.pool.max_pages)):
                raise ValueError(
                    f"request {rid}: needs "
                    f"{self.pool.pages_for(len(prompt) + max_new + window)}"
                    f" pages, pool holds {self.pool.n_pages} "
                    f"(max {self.pool.max_pages}/slot)")
        self._max_new = max_new
        tr = self.telemetry
        try:
            while self.queue or any(r is not None for r in self.slot_rid):
                self.round += 1
                with phase("round", tr, self.round):
                    if not self._round(max_new, steps):
                        break
        except PageError as err:
            # postmortem before the crash propagates: the flight
            # recorder's ring holds the last N lifecycle events leading
            # up to the invariant trip — dump them with the allocator
            # and slot-table state so every CI failure ships its own
            # debugging bundle
            self._dump_flight(err)
            raise
        return self.results

    def _round(self, max_new: int, steps: int) -> bool:
        """One scheduling round; False once nothing is left to decode."""
        tr = self.telemetry
        if self.chaos is not None:
            with phase("chaos", tr, self.round):
                self.chaos.on_round(self)
        with phase("sweep", tr, self.round):
            if self.overload is not None:
                self._overload_round()
            self._cancel_sweep(max_new)
            # progress watchdog (replaces the old idle-spin counter +
            # RuntimeError): *any* kind of stall — admission spin,
            # livelock, chaos stall — trips it after watchdog_rounds
            # rounds with unchanged progress counters, dumps the
            # flight bundle, and sheds the blocking head so the run
            # finishes instead of raising
            self._watchdog_tick()
        if self.round < self._stall_until:
            return True                           # chaos stall: dead round
        self._refill(max_new)
        if not self._decoding_rows():
            # nothing is decoding: if slots are still PREFILLING (or the
            # queue is waiting on pages) the next refill round advances
            # their chunks — a decode segment would only burn a scan on
            # all-done rows
            return bool(self.queue
                        or any(r is not None for r in self.slot_rid))
        with phase("pages", tr, self.round) as span:
            # optimistic admission: make every decoding slot's page
            # table cover this segment's worst-case advance, preempting
            # on pressure — may evict every decoding slot (chaos holds),
            # in which case the next refill round re-admits from the
            # queue
            self._ensure_decode_pages(steps)
            rows = self._decoding_rows()
            walk = {}
            if rows:
                live, mapped, _ = self._sample_kv()
                span.set_metadata(live_tokens=live, mapped_tokens=mapped)
                if self.pool is not None:
                    cap = self._page_cap()
                    walk = self._decode_pages(cap)
                    pages = jnp.asarray(self.pool.table[:, :cap])
                else:
                    cap = self._kv_cap(steps)
        if not rows:
            return True
        with phase("decode-segment", tr, self.round, **walk):
            loop = self._loop(steps, cap)
            if self._spec_live():
                hist = jnp.asarray(self.history)
                ((self.tok, self.caches, self.lengths, self.done,
                  self.remaining, self.key, hist), emitted) = loop(
                    self.params, self.tok, self.caches, self.lengths,
                    self.done, self.remaining, self.key, hist, pages)
                # np.array (not asarray): the device export is
                # read-only and the next join writes prompts into this
                # mirror
                self.history = np.array(hist)
            elif self.pool is not None:
                ((self.tok, self.caches, self.lengths, self.done,
                  self.remaining, self.key), emitted) = loop(
                    self.params, self.tok, self.caches, self.lengths,
                    self.done, self.remaining, self.key, pages)
            else:
                ((self.tok, self.caches, self.lengths, self.done,
                  self.remaining, self.key), emitted) = loop(
                    self.params, self.tok, self.caches, self.lengths,
                    self.done, self.remaining, self.key)
            emitted = np.asarray(emitted)
        with phase("collect", tr, self.round):
            self._collect(emitted)
        return True

    def _decoding_rows(self) -> int:
        """Slots holding a request past its prefill."""
        return sum(1 for i, r in enumerate(self.slot_rid)
                   if r is not None and not self.slot_pending[i])

    def _decode_pages(self, cap: int) -> dict:
        """The pages the paged decode kernel reads for the decoding slots
        at this segment's first step (``pages_live``: ceil(len / ps) per
        slot) and the pages its block walk spans (``pages_walked``: each
        slot's count rounded up to whole blocks of the kernel's
        ``pages_per_block`` under a table sliced to ``cap`` pages)."""
        arch = self.model.cfg
        ps = self.pool.page_size
        _, ppb = decode_launch(hq=arch.n_heads, hkv=arch.kv_heads,
                               d=arch.resolved_head_dim, page_size=ps,
                               p_cap=cap)
        lens = np.array([self.slot_len[i]
                         for i, r in enumerate(self.slot_rid)
                         if r is not None and not self.slot_pending[i]],
                        np.int64)
        live = -(-lens // ps)
        walked = np.minimum(-(-live // ppb) * ppb, cap)
        return {"pages_live": int(live.sum()),
                "pages_walked": int(walked.sum())}

    # ------------------------------------------------------------------
    # flight recorder
    # ------------------------------------------------------------------
    def _dump_flight(self, err: BaseException) -> dict | None:
        """Assemble (and optionally write) the flight-recorder debug
        bundle: the ring buffer's last events, the allocator snapshot,
        the host slot table, the config and the metrics at the moment a
        PageError escaped the run loop.  Stored on
        ``self.last_flight_bundle``; written as JSON when
        ``cfg.flight_path`` (or $REPRO_FLIGHT_PATH) names a file."""
        if self.flight is None:
            return None
        cfg = {k: (v if isinstance(v, (bool, int, float, str, type(None)))
                   else str(v))
               for k, v in dataclasses.asdict(self.cfg).items()}
        bundle = {
            "schema": 1,
            "error": f"{type(err).__name__}: {err}",
            "round": self.round,
            "config": cfg,
            "events": self.flight.tail(),
            "slot_table": {
                "slot_rid": list(self.slot_rid),
                "slot_len": list(self.slot_len),
                "slot_filled": list(self.slot_filled),
                "slot_budget": list(self.slot_budget),
                "slot_prior": list(self.slot_prior),
                "slot_max_tokens": list(self.slot_max_tokens),
                "pending_tokens": [len(p) for p in self.slot_pending]},
            "pool": self.pool.snapshot() if self.pool is not None else None,
            "queue": [[rid, len(p)] for rid, p in self.queue],
            "preempt_events": list(self.preempt_events),
            "metrics": self.metrics.snapshot(),
        }
        self.last_flight_bundle = bundle
        path = self.cfg.flight_path or os.environ.get("REPRO_FLIGHT_PATH")
        if path:
            with open(path, "w") as f:
                json.dump(bundle, f, indent=1)
                f.write("\n")
        return bundle

    # ------------------------------------------------------------------
    # KV memory accounting
    # ------------------------------------------------------------------
    def _sample_kv(self) -> tuple[int, int, int]:
        """Record and return (live tokens, allocated token capacity, live
        slots) at a segment boundary.  Dense allocates ``batch * max_len``
        whether or not slots are live; paged allocates only the mapped
        pages."""
        live = [i for i, r in enumerate(self.slot_rid) if r is not None]
        live_tokens = sum(self.slot_len[i] for i in live)
        if self.pool is not None:
            alloc = self.pool.used_pages * self.pool.page_size
        else:
            alloc = self.cfg.batch * self.cfg.max_len
        sample = (live_tokens, alloc, len(live))
        self.kv_samples.append(sample)
        return sample

    def kv_utilization(self) -> dict:
        """Aggregate the per-segment samples: mean/peak KV utilization
        (live tokens / allocated token capacity) and peak concurrency."""
        if not self.kv_samples:
            return {"mean_util": 0.0, "peak_util": 0.0,
                    "peak_live_slots": 0, "samples": 0}
        utils = [lt / cap for lt, cap, _ in self.kv_samples if cap]
        return {"mean_util": sum(utils) / max(1, len(utils)),
                "peak_util": max(utils, default=0.0),
                "peak_live_slots": max(s for _, _, s in self.kv_samples),
                "samples": len(self.kv_samples)}

    def join_stats(self) -> dict:
        """Join-segment latency trajectory: every refill that ran a join
        stalls all live slots' decode for its duration — the number
        chunked prefill exists to bound.  ``chunk_joins`` counts the
        continuation pieces (0 when unchunked); ``budget_deferrals``
        counts prefill pieces pushed to a later round by the
        decode-priority ``prefill_round_tokens`` cap (0 when uncapped);
        ``widths`` lists the padded prefill widths (jit buckets) the
        joins ran at."""
        m = self.metrics
        n = m.count("join.seconds")
        return {"joins": n,
                "widths": sorted({int(w) for w in m.samples("join.width")}),
                "chunk_joins": int(m.value("join.chunk_continuations")),
                "budget_deferrals": int(m.value("join.budget_deferrals")),
                "max_join_s": max(m.samples("join.seconds"), default=0.0),
                "mean_join_s": m.sum("join.seconds") / n if n else 0.0}

    def reset_stats(self) -> None:
        """Zero *all* per-wave measurement state.  Benchmarks re-submit
        requests into a *warm* batcher to measure the steady serving
        state (a fresh instance would re-jit its closures and time
        compilation); without this reset the second wave's stats would
        blend with the first's.

        The accumulated stats all live in the metrics registry, so one
        ``metrics.reset()`` clears every counter and histogram — latency
        and queue-wait samples, join times, speculative acceptance,
        preemption/recompute tallies, budget deferrals, prefill/prefix
        accounting (the pre-registry version hand-picked a subset and
        silently missed the rest).  What it deliberately does *not*
        touch is operational state the next wave still depends on:
        ``_resumed`` / ``_preempt_counts`` / ``_submit_t`` (in-flight
        request bookkeeping), ``_skips`` / ``admit_order`` (admission
        history), the slot table, and the round counter (the chaos
        injector keys on it)."""
        self._clock0 = None
        self._first_tok_t.clear()
        self.metrics.reset()
        # pool-partition gauges describe *current* allocator state, but
        # this batcher owns them — clear and immediately re-seed from the
        # live pool, so a gauge from a previous pool geometry can never
        # survive into the next wave's snapshot()
        self.metrics.clear_gauges("pool.")
        if self.pool is not None and self.pool.gauge_cb is not None:
            self.pool._notify()
        self._slo_classes.clear()
        self.kv_samples = []
        self.preempt_events.clear()
        self.preempted_rids.clear()
        # overload measurement state resets with the wave; the deadline/
        # timeout *stamps* are in-flight request bookkeeping and survive
        # (like _resumed / _preempt_counts above)
        self.cancelled.clear()
        self.rejections.clear()
        if self.overload is not None:
            self.overload.reset()

    def spec_stats(self) -> dict:
        """Self-speculation effectiveness: ``acceptance_rate`` = accepted
        drafts / proposed drafts, and ``tokens_per_step`` = committed
        tokens per verify step (1.0 = speculation never helped, k+1 =
        every draft always accepted).  All zeros with speculation off, so
        the dict is reportable either way."""
        m = self.metrics
        steps = int(m.value("spec.steps"))
        proposed = int(m.value("spec.proposed"))
        accepted = int(m.value("spec.accepted"))
        emitted = int(m.value("spec.emitted"))
        return {"enabled": bool(self.spec_k),
                "k": self.spec_k,
                "steps": steps,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": (accepted / proposed
                                    if proposed else 0.0),
                "tokens_per_step": (emitted / steps
                                    if steps else 0.0)}

    def latency_stats(self) -> dict:
        """Per-request latency trajectory observed at host sync points:
        TTFT (run start -> the join that sampled the request's first
        token), time-per-output-token ((retirement - first token) /
        (tokens - 1), requests with > 1 token), and queue wait (submit —
        or preemption — to admission; a preempted request contributes one
        wait per admission).  Segment syncs quantize all of these —
        serving-level numbers, not kernel timings.  Preemption counters
        ride along so one dict describes what the request latencies paid
        for (percentiles come from the registry's histograms — the one
        ``_pct`` implementation, no per-method sample plumbing)."""
        m = self.metrics
        return {"requests": m.count("lat.ttft_s"),
                "ttft_p50_s": m.percentile("lat.ttft_s", 50),
                "ttft_p95_s": m.percentile("lat.ttft_s", 95),
                "tpot_p50_s": m.percentile("lat.tpot_s", 50),
                "tpot_p95_s": m.percentile("lat.tpot_s", 95),
                "queue_wait_p50_s": m.percentile("lat.queue_wait_s", 50),
                "queue_wait_p95_s": m.percentile("lat.queue_wait_s", 95),
                "preemptions": int(m.value("preempt.count")),
                "preempted_token_recompute":
                    int(m.value("preempt.recompute_tokens")),
                "cancellations": int(m.value("cancel.count")),
                "shed_requests": int(m.value("cancel.shed")),
                "deadline_met": int(m.value("deadline.met")),
                "deadline_total": int(m.value("deadline.total")),
                "deadline_attainment": self._deadline_attainment(),
                "watchdog_trips": int(m.value("watchdog.trips"))}

    def _deadline_attainment(self) -> float:
        """Met/total over deadline-carrying requests that were *scored*:
        retired (met iff on time) or cancelled for deadline/timeout
        (always a miss).  Shed and client cancels are excluded — a
        RETRY_AFTER rejection is a fast answer, not a latency violation.
        Vacuously 1.0 with no deadlines in play."""
        total = int(self.metrics.value("deadline.total"))
        met = int(self.metrics.value("deadline.met"))
        return met / total if total else 1.0

    def overload_stats(self) -> dict:
        """One dict for the overload-protection story: cancellation and
        shed tallies, deadline attainment, watchdog trips, the RETRY_AFTER
        rejection ledger, and the degradation controller's state machine
        (state, time-in-state, transition history, whether it recovered
        to HEALTHY).  Controller-off runs report HEALTHY with zero
        time-in-state, so the dict is reportable either way."""
        m = self.metrics
        if self.overload is not None:
            ctl = self.overload.stats()
        else:
            ctl = {"state": HEALTHY, "recovered_to_healthy": False,
                   "transitions": [],
                   "time_in_state": {s: 0.0 for s in STATES}}
        return {"enabled": self.overload is not None,
                "cancellations": int(m.value("cancel.count")),
                "cancelled_by_reason": {
                    r: int(m.value(f"cancel.{r}")) for r in CANCEL_REASONS},
                "shed_requests": int(m.value("cancel.shed")),
                "deadline_met": int(m.value("deadline.met")),
                "deadline_total": int(m.value("deadline.total")),
                "deadline_attainment": self._deadline_attainment(),
                "watchdog_trips": int(m.value("watchdog.trips")),
                "rejections": list(self.rejections),
                "controller": ctl}

    def slo_stats(self, window: int = 64) -> dict:
        """SLO attainment and burn rate against ``cfg.ttft_slo_s`` /
        ``cfg.tpot_slo_s``.

        * ``slo_attainment`` — overall met/total fraction across both
          metrics and every priority class, always in [0, 1] (vacuously
          1.0 with no SLO configured or no samples yet — "no target" is
          never a violation);
        * ``classes`` — per-priority-class met/total/attainment, so a
          mixed-priority wave shows *which* class is paying for the
          preemptions (victims are picked lowest-priority-first, so
          attainment should be monotone in class under pressure);
        * ``burn_rate_*`` — violating fraction of the last ``window``
          raw samples, normalized by the error budget ``1 - slo_target``
          (1.0 = burning exactly the budget, > 1.0 = on track to miss
          the target) — the windowed view reacts to a regression long
          before the cumulative attainment moves.
        """
        cfg, m = self.cfg, self.metrics
        enabled = (cfg.ttft_slo_s is not None
                   or cfg.tpot_slo_s is not None)
        classes: dict[int, dict] = {}
        met_all = total_all = 0
        for cls in sorted(self._slo_classes):
            row: dict = {}
            for metric in ("ttft", "tpot"):
                tot = int(m.value(f"slo.{metric}_total.c{cls}"))
                met = int(m.value(f"slo.{metric}_met.c{cls}"))
                row[f"{metric}_met"] = met
                row[f"{metric}_total"] = tot
                row[f"{metric}_attainment"] = met / tot if tot else 1.0
                met_all += met
                total_all += tot
            classes[cls] = row
        budget = max(1e-9, 1.0 - cfg.slo_target)
        burn = {}
        for metric, slo in (("ttft", cfg.ttft_slo_s),
                            ("tpot", cfg.tpot_slo_s)):
            if slo is None:
                burn[metric] = 0.0
                continue
            recent = m.samples(f"lat.{metric}_s")[-window:]
            viol = (sum(1 for v in recent if v > slo) / len(recent)
                    if recent else 0.0)
            burn[metric] = viol / budget
        return {"enabled": enabled,
                "ttft_slo_s": cfg.ttft_slo_s,
                "tpot_slo_s": cfg.tpot_slo_s,
                "slo_target": cfg.slo_target,
                "slo_attainment": (met_all / total_all
                                   if total_all else 1.0),
                "classes": classes,
                "window": window,
                "burn_rate_ttft": burn["ttft"],
                "burn_rate_tpot": burn["tpot"]}

    def preempt_stats(self) -> dict:
        """Preemption effectiveness and liveness: how many evictions
        happened, how much prefill was re-spent resuming them, and
        ``recomputed_ok`` — True iff every request that was ever
        preempted has retired with a result (vacuously True with no
        preemptions; the liveness gate pairs it with
        ``preemptions > 0``)."""
        # a preempted-then-cancelled request is accounted for (its pages
        # were released and it reached a terminal state) even though it
        # never produced a result
        ok = all((rid in self.results or rid in self.cancelled)
                 and rid not in self._resumed
                 for rid in self.preempted_rids)
        return {"enabled": self.cfg.admission_mode == "optimistic",
                "preemptions": self.preemptions,
                "preempted_requests": len(self.preempted_rids),
                "recompute_tokens": self.preempted_token_recompute,
                "slot_failures": (self.chaos.slot_failures
                                  if self.chaos is not None else 0),
                "recomputed_ok": ok,
                "events": list(self.preempt_events)}

    def prefix_stats(self) -> dict:
        """Prefix-cache effectiveness: prefill tokens computed vs skipped
        (token hit rate), request-level hits, and cache residency.  With
        the cache off everything lands in ``prefill_computed`` and the
        rates are zero, so the dict is reportable either way."""
        total = self.prefill_computed + self.prefill_skipped
        return {"enabled": self.prefix is not None,
                "prefill_computed": self.prefill_computed,
                "prefill_skipped": self.prefill_skipped,
                "hit_rate": self.prefill_skipped / total if total else 0.0,
                "admits": self.prefix_admits,
                "hits": self.prefix_hits,
                "cached_pages": (self.pool.cached_pages
                                 if self.pool is not None else 0),
                "radix_entries": (self.prefix.n_entries
                                  if self.prefix is not None else 0),
                "evicted_pages": (self.prefix.evicted_pages
                                  if self.prefix is not None else 0)}


# the public serving entry point: the slot scheduler *is* the batcher
Batcher = ContinuousBatcher

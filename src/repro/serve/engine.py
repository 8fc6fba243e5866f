"""Serving engine: jitted prefill/decode steps and the device-resident
multi-token decode loop.

``decode_step`` is the paper's regime: one token against a deep KV cache is
a skinny, memory-bandwidth-bound op (op/byte ~= 1-2) — exactly what the
PIM-amenability test flags, and what the decode_attn Pallas kernel and the
roofline's memory term are about.  The §5 co-design lesson is that
orchestration, not kernel peak, decides delivered speed: a per-token Python
loop spends its time in host dispatch and host argmax, so ``decode_loop``
keeps everything — tokens, caches, per-slot lengths, done flags, sampling —
on device inside one jitted ``lax.scan`` and only syncs to host every
``sync_every`` steps.  Caches are donated throughout, so decode runs
in-place.

The slot-based continuous-batching scheduler that drives this loop lives in
:mod:`repro.serve.scheduler`; ``Batcher`` (the public entry point) is
re-exported from there.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..distributed import sharding as shd
from ..kernels.decode_attn import decode_attn_policy
from ..models.model_zoo import Model

PAD_TOKEN = -1    # emitted-slot sentinel: "slot was already retired"
# rows per prefill group of the paged join (make_paged_join)
JOIN_GROUP_ROWS = 4


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    batch: int
    dtype: Any = jnp.bfloat16
    temperature: float = 0.0     # 0 = greedy
    sync_every: int = 8          # decode steps per host sync (scan length)
    attn_mode: str = "auto"      # decode attention: "kernel"|"xla"|"auto"
    attn_interpret: bool | None = None   # None -> off on TPU, on elsewhere
    # paged KV cache (repro.serve.kvpool): fixed-size pages in one pooled
    # allocation, per-slot page tables, admission on free-page capacity
    paged: bool = False
    page_size: int = 16          # KV rows per page
    total_pages: int | None = None   # pool size; None -> batch * max pages
    #   (i.e. the same token capacity as the dense slot table)
    # shared-prefix radix cache (repro.serve.prefixcache, needs paged):
    # full prompt pages are registered in a radix tree, later requests map
    # the matched pages via KVPool.share and prefill only their suffix
    prefix_cache: bool = False
    # admission policy: "fifo" keeps strict head-of-line order; the opt-in
    # "skip-ahead" scans up to ``admission_lookahead`` queued requests for
    # the first one whose pages fit when the head does not (higher slot
    # occupancy under mixed prompt sizes, bounded reorder window)
    admission: str = "fifo"
    # admission sizing (needs paged): "reserve" (default) maps the whole
    # worst case (prompt + max_new + speculation window) at admission, so
    # a slot can never run out of pages but the pool runs far under its
    # real capacity whenever outputs finish early.  "optimistic" maps only
    # the prompt's pages at admission and grows each slot's table
    # on demand between decode segments; when growth outruns the pool the
    # scheduler preempts a victim slot (lowest priority, then most pages,
    # then least progress), parks its dead pages in the pool's preempted
    # partition and re-queues it — resume recomputes the KV from the
    # host-mirrored history through the chunked-prefill join path, with
    # prefix-cache hits shortcutting the recompute.  Attention-only (a
    # recurrent state cannot be recomputed from a page-aligned resume).
    admission_mode: str = "reserve"
    admission_lookahead: int = 8
    # skip-ahead aging: a bypassed head's priority grows with every skip;
    # once it has been skipped ``admission_max_skips`` times it becomes a
    # barrier (nothing is admitted past it until it fits), so sustained
    # small-request load cannot starve a big prompt.  0 degenerates
    # skip-ahead to FIFO.
    admission_max_skips: int = 8
    # chunked prefill (needs paged): a joining prompt's uncached suffix is
    # prefilled at most ``prefill_chunk`` tokens per join round, the slot
    # parking in the PREFILLING state (device done-latch frozen) between
    # chunks so live slots' decode segments interleave with the remaining
    # chunks instead of stalling behind one long prompt.  Must be a
    # multiple of ``page_size`` (chunk boundaries then never land inside a
    # shared prefix page); None = whole suffix in one join (PR 3
    # behavior).
    prefill_chunk: int | None = None
    # decode-priority chunk budget: cap the *total* prefill tokens (chunk
    # continuations + new admissions) a single refill round may take, so
    # many PREFILLING slots cannot monopolize a round and starve decode
    # latency.  Admission stops once the cap is reached (the first piece
    # of a round always goes through, so progress is guaranteed); deferred
    # pieces ride the next round and are counted in ``join_stats()``.
    # None (default) keeps the one-chunk-per-slot-per-round behavior.
    prefill_round_tokens: int | None = None
    # self-speculative decoding (needs paged; greedy/attention-only): each
    # decode step drafts ``speculate_k`` candidate tokens from the slot's
    # own prompt+output history (on-device n-gram lookup, see
    # :func:`ngram_propose`) and verifies all k+1 tokens in ONE multi-token
    # paged attention call — the PR 4 flash-prefill kernel at Lq = k+1,
    # unchanged.  Greedy agreement decides the per-slot accepted length;
    # accepted tokens commit, ``lengths`` advances by exactly that many,
    # and the speculative K/V rows past the acceptance point are simply
    # overwritten by the next step's verify (rollback = don't advance).
    # Output is bit-identical to speculate-off greedy decode; only the
    # steps-per-token changes.  ``speculate_ngram`` is the match width of
    # the history lookup.
    speculate_k: int | None = None
    speculate_ngram: int = 2
    # unified telemetry (repro.serve.telemetry): when True the batcher
    # builds a Tracer recording per-request lifecycle events, per-round
    # scheduler spans and pool-partition gauges (exportable as Perfetto
    # trace_event JSON).  Off by default; on or off, the jitted closures
    # and the host-device syncs are the same (all instrumentation sits
    # at scheduling-round boundaries, never inside lax.scan).
    telemetry: bool = False
    # SLO monitor (repro.serve.scheduler.slo_stats): per-request latency
    # targets.  None disables the check for that metric (attainment is
    # vacuously 1.0); with a target set, every observed TTFT/TPOT is
    # scored against it per priority class, and ``slo_target`` is the
    # attainment objective the windowed burn rate is normalized by
    # (burn rate 1.0 = violating exactly the error budget, > 1.0 =
    # burning it faster than the target allows).
    ttft_slo_s: float | None = None
    tpot_slo_s: float | None = None
    slo_target: float = 0.9
    # flight recorder: an always-on bounded ring buffer of lifecycle
    # events (cheap enough to run untraced — host dict appends at
    # scheduling-round boundaries, no device syncs, no pool gauge
    # callback).  When a PageError escapes the run loop (pool/prefix
    # invariant trip, allocator exhaustion with no victim), the batcher
    # dumps the last ``flight_events`` events + pool snapshot + slot
    # table + config as a debug bundle (``Batcher.last_flight_bundle``,
    # written to ``flight_path`` / $REPRO_FLIGHT_PATH when set) before
    # re-raising — every CI failure ships its own postmortem.
    flight_recorder: bool = True
    flight_events: int = 256
    flight_path: str | None = None
    # overload protection (repro.serve.overload): when True the batcher
    # runs a DegradationController — a hysteresis ladder HEALTHY ->
    # DEGRADED -> SHEDDING driven by the windowed SLO burn rate and the
    # pool-pressure gauge.  DEGRADED sheds speculation and shrinks the
    # prefill chunk; SHEDDING additionally freezes optimistic slot
    # growth (admission reverts to worst-case reservation) and sheds
    # lowest-priority queued work with a retryable RETRY_AFTER
    # rejection.  Degradation changes when/whether work runs, never its
    # tokens — completing requests stay bit-exact.  Deadline/timeout
    # cancellation (submit(deadline_s=..., timeout_s=...)) is always on;
    # the controller is the opt-in *load-shedding* half.
    overload: bool = False
    overload_degrade_burn: float = 1.0   # burn rate that enters DEGRADED
    overload_shed_burn: float = 2.0      # burn rate that enters SHEDDING
    overload_degrade_pressure: float = 0.9   # pool mapped+held fraction
    overload_shed_pressure: float = 1.0      # ... with work still queued
    overload_up_rounds: int = 2          # consecutive hot rounds to climb
    overload_down_rounds: int = 4        # consecutive cool rounds to drop
    # SHEDDING drains the queue down to this depth (None -> cfg.batch),
    # lowest-priority / latest-submitted first, never a preempted resume
    overload_queue_keep: int | None = None
    overload_retry_after_s: float = 1.0  # RETRY_AFTER hint on shed
    # progress watchdog (replaces the idle-spin guard): rounds without
    # any join / commit / retirement / preemption / cancellation before
    # the scheduler dumps the flight bundle and force-sheds the blocking
    # head instead of raising
    watchdog_rounds: int = 100_000

    @property
    def max_pages(self) -> int:
        """Page-table width: pages needed for a full-length slot."""
        return -(-self.max_len // self.page_size)

    @property
    def pool_pages(self) -> int:
        return (self.total_pages if self.total_pages is not None
                else self.batch * self.max_pages)


def sample_tokens(logits: jnp.ndarray, key: jax.Array,
                  temperature: float) -> jnp.ndarray:
    """logits [B, V] -> token ids [B] (on device; greedy when T == 0)."""
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# single-step factories (kept for the dry-run / sharding paths)
# ---------------------------------------------------------------------------

def make_decode_step(model: Model, cfg: ServeConfig):
    def step(params, tokens, caches, cache_len, extra):
        logits, caches = model.decode_step(params, tokens, caches, cache_len,
                                           dtype=cfg.dtype,
                                           extra=extra or None)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], caches
    return step


def jit_decode_step(model: Model, cfg: ServeConfig, mesh: Mesh,
                    input_specs: dict):
    step = make_decode_step(model, cfg)
    pshard = shd.param_shardings(model.abstract_ptree(), mesh)
    tok_shard = shd.data_shardings(input_specs["tokens"], mesh)
    cache_shard = shd.cache_shardings(input_specs["caches"], mesh)
    extra_shard = shd.data_shardings(input_specs.get("extra", {}), mesh)
    return jax.jit(
        step,
        in_shardings=(pshard, tok_shard, cache_shard,
                      shd.replicated(mesh), extra_shard),
        out_shardings=(tok_shard, cache_shard),
        donate_argnums=(2,))


def make_prefill(model: Model, cfg: ServeConfig):
    def prefill(params, batch):
        return model.prefill(params, batch, cfg.max_len, dtype=cfg.dtype)
    return prefill


# ---------------------------------------------------------------------------
# self-speculative drafting (on-device n-gram / prompt-lookup)
# ---------------------------------------------------------------------------

def ngram_propose(history: jnp.ndarray, lengths: jnp.ndarray, *,
                  k: int, n: int) -> jnp.ndarray:
    """Draft ``k`` continuation tokens per slot from the slot's own token
    history — no draft model, just prompt/output lookup.

    ``history`` [B, S] holds each slot's known tokens (prompt, then every
    committed output token); position ``lengths[b]`` is the current token,
    everything past it is unknown (stale values there are never read).
    The tail ``n``-gram ``history[b, L-n+1 .. L]`` is matched against every
    earlier window; the *most recent* match at start ``p`` gives a period
    estimate ``d = (L - n + 1) - p``, and the draft extrapolates that
    period: predicted position ``L + 1 + t`` copies position
    ``L + 1 + t - d`` (from history when that lands at or below ``L``,
    from an earlier draft of this very call otherwise — the unrolled
    ``t`` loop makes that self-reference static).  No match degenerates
    to ``d = 1``, i.e. repeat-the-current-token.

    Drafts are *proposals only*: the verify pass accepts exactly the
    prefix the model itself would have produced, so a bad draft costs
    speed, never correctness.  Work is O(S * n) integer compares per
    call — noise next to the attention sweep it amortizes.
    """
    b, s = history.shape
    ln = jnp.asarray(lengths, jnp.int32)
    idx = jnp.arange(s)
    match = jnp.ones((b, s), bool)
    for j in range(n):
        shifted = history[:, jnp.minimum(idx + j, s - 1)]          # [B, S]
        tail_j = jnp.take_along_axis(
            history, jnp.clip(ln - n + 1 + j, 0, s - 1)[:, None], axis=1)
        match &= shifted == tail_j
    # candidate starts: window fully below the tail's own window, so the
    # continuation position p + n is a known token (p <= L - n)
    valid = idx[None, :] <= (ln - n)[:, None]
    p = jnp.where(match & valid, idx[None, :], -1).max(axis=1)     # [B]
    d = jnp.where(p >= 0, ln - n + 1 - p, 1).astype(jnp.int32)     # >= 1
    drafts: list[jnp.ndarray] = []
    for t in range(k):
        src = ln + 1 + t - d                                       # [B]
        from_hist = jnp.take_along_axis(
            history, jnp.clip(src, 0, s - 1)[:, None], axis=1)[:, 0]
        if drafts:
            prev = jnp.stack(drafts, axis=1)                       # [B, t]
            from_draft = jnp.take_along_axis(
                prev, jnp.clip(t - d, 0, t - 1)[:, None], axis=1)[:, 0]
        else:
            from_draft = from_hist
        drafts.append(jnp.where(src <= ln, from_hist, from_draft))
    return jnp.stack(drafts, axis=1)                               # [B, k]


# ---------------------------------------------------------------------------
# device-resident decode loop
# ---------------------------------------------------------------------------

def make_decode_loop(model: Model, cfg: ServeConfig, *, steps: int,
                     eos_id: int | None, kv_cap: int | None = None,
                     paged: bool = False, speculate_k: int = 0):
    """Build the fused multi-token decode driver.

    Returns ``loop(params, tok, caches, lengths, done, remaining, key
    [, pages]) -> ((tok, caches, lengths, done, remaining, key), emitted)``
    where ``emitted`` is [steps, B] int32 with PAD_TOKEN in retired slots.
    All state stays on device across the scan; per-slot ``lengths`` drive
    the cache writes, RoPE positions and attention masks, ``done`` freezes
    retired slots (EOS or budget), and sampling happens on device.

    With ``paged`` the loop additionally takes ``pages`` — the [B, P_cap]
    slice of the device page table, held constant across the scan (the
    scheduler reserves every slot's worst case at admission, so a segment
    can never outgrow its pages).  ``P_cap`` then plays ``kv_cap``'s role,
    but the pruning is shape-driven instead of policy-driven: the
    scheduler buckets the deepest live slot's *page count* to a power of
    two and slices the table before the call, so the paged-attention grid
    (and the XLA gather width) is the bucket — dead pages are never
    launched.  One executable is cached per (steps, P_cap) bucket, exactly
    like the dense loop's (steps, kv_cap) keying.

    With ``speculate_k`` = k > 0 (paged + greedy only) each scan step is a
    draft-k **verify** step instead of a one-token decode: the carry grows
    a per-slot token ``history`` [B, max_len], :func:`ngram_propose`
    drafts k candidates from it, and one ``model.decode_step`` call with
    Lq = k+1 tokens (the current token + the drafts, at absolute depth
    ``lengths`` — the PR 4 paged flash-prefill kernel *is* the verify
    kernel) yields greedy outputs for every position.  The accepted length
    is the longest prefix where draft t equals the model's own output at
    position t-1; the step commits ``accepted + 1`` tokens (the +1 is the
    model's bonus token after the last accepted draft), clipped by EOS
    inside the window, the remaining budget and ``max_len``.  ``lengths``
    advances by exactly the committed count — the K/V rows the verify
    wrote past the acceptance point stay stale and are overwritten by the
    next step's verify, whose write window starts at the new ``lengths``
    (rollback by not advancing; admission reserved the k-token overhang).
    ``emitted`` becomes [steps, B, k+1] with PAD past each step's
    committed count.  Token-for-token this is bit-identical to the
    speculate-off greedy loop: every committed token is the argmax the
    plain loop would have produced at that position.
    """
    temp = cfg.temperature
    spec_n = cfg.speculate_ngram

    def loop(params, tok, caches, lengths, done, remaining, key,
             pages=None):
        def body(carry, _):
            tok, caches, lengths, done, remaining, key = carry
            with decode_attn_policy(mode=cfg.attn_mode,
                                    interpret=cfg.attn_interpret,
                                    kv_cap=None if paged else kv_cap):
                logits, caches = model.decode_step(
                    params, tok, caches, lengths, dtype=cfg.dtype,
                    pages=pages)
            key, sub = jax.random.split(key)
            nxt = sample_tokens(logits[:, -1], sub, temp)
            emit = jnp.where(done, PAD_TOKEN, nxt)
            if eos_id is None:
                is_eos = jnp.zeros_like(done)
            else:
                is_eos = nxt == eos_id
            remaining = remaining - jnp.where(done, 0, 1)
            lengths = lengths + jnp.where(done, 0, 1)
            new_done = (done | is_eos | (remaining <= 0)
                        | (lengths >= cfg.max_len))
            tok = jnp.where(done[:, None], tok, nxt[:, None])
            return (tok, caches, lengths, new_done, remaining, key), emit

        carry = (tok, caches, lengths, done, remaining, key)
        carry, emitted = jax.lax.scan(body, carry, None, length=steps)
        return carry, emitted

    if not speculate_k:
        return loop
    if not paged:
        raise ValueError("speculate_k requires the paged loop")
    k = speculate_k

    def spec_loop(params, tok, caches, lengths, done, remaining, key,
                  history, pages):
        def body(carry, _):
            tok, caches, lengths, done, remaining, key, history = carry
            drafts = ngram_propose(history, lengths, k=k, n=spec_n)
            qtok = jnp.concatenate([tok, drafts], axis=1)      # [B, k+1]
            with decode_attn_policy(mode=cfg.attn_mode,
                                    interpret=cfg.attn_interpret):
                # Lq = k+1 at per-slot depth ``lengths``: K/V scatters at
                # positions lengths..lengths+k, causal attention through
                # the page table — the flash-prefill verify call
                logits, caches = model.decode_step(
                    params, qtok, caches, lengths, dtype=cfg.dtype,
                    pages=pages)
            key, _ = jax.random.split(key)     # greedy: keep key moving
            out = jnp.argmax(logits.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)        # [B, k+1]
            # accepted = longest prefix where the draft matches the
            # model's own greedy output one position earlier; commit the
            # accepted drafts plus the model's bonus token after them
            agree = (drafts == out[:, :-1]).astype(jnp.int32)  # [B, k]
            adv = jnp.cumprod(agree, axis=1).sum(axis=1) + 1   # [B] 1..k+1
            if eos_id is not None:
                hit = out == eos_id
                first_eos = jnp.argmax(hit, axis=1)
                adv = jnp.minimum(adv, jnp.where(hit.any(axis=1),
                                                 first_eos + 1, k + 1))
            adv = jnp.minimum(adv, remaining)              # token budget
            adv = jnp.minimum(adv, cfg.max_len - lengths)  # window cap
            adv = jnp.where(done, 0, adv)
            jidx = jnp.arange(k + 1)[None, :]
            commit = jidx < adv[:, None]                   # [B, k+1]
            emit = jnp.where(commit, out, PAD_TOKEN)
            last = jnp.take_along_axis(
                out, jnp.maximum(adv - 1, 0)[:, None], axis=1)  # [B, 1]
            # committed token j becomes known history at position
            # lengths + 1 + j (position lengths holds the current token);
            # non-committed columns scatter out of bounds and drop
            bi = jnp.arange(out.shape[0])[:, None]
            wpos = jnp.where(commit, lengths[:, None] + 1 + jidx,
                             history.shape[1])
            history = history.at[bi, wpos].set(out, mode="drop")
            if eos_id is None:
                eos_last = jnp.zeros_like(done)
            else:
                # an EOS inside the window truncated adv at itself, so if
                # it was committed at all it is the last committed token
                eos_last = (last[:, 0] == eos_id) & (adv > 0)
            remaining = remaining - adv
            lengths = lengths + adv
            new_done = (done | eos_last | (remaining <= 0)
                        | (lengths >= cfg.max_len))
            tok = jnp.where((adv > 0)[:, None], last, tok)
            return (tok, caches, lengths, new_done, remaining, key,
                    history), emit

        carry = (tok, caches, lengths, done, remaining, key, history)
        carry, emitted = jax.lax.scan(body, carry, None, length=steps)
        return carry, emitted                  # emitted [steps, B, k+1]
    return spec_loop


def jit_decode_loop(model: Model, cfg: ServeConfig, *, steps: int,
                    eos_id: int | None, kv_cap: int | None = None):
    """Jitted decode segment: the caches argument is donated so the KV
    cache is updated in place across the whole scan (the small carry
    arrays — tokens, lengths, flags, key — are copied)."""
    loop = make_decode_loop(model, cfg, steps=steps, eos_id=eos_id,
                            kv_cap=kv_cap)
    return jax.jit(loop, donate_argnums=(2,))


def jit_paged_decode_loop(model: Model, cfg: ServeConfig, *, steps: int,
                          eos_id: int | None):
    """Jitted paged decode segment — :func:`make_decode_loop` with
    ``paged=True`` (the call site passes the sliced page table)."""
    loop = make_decode_loop(model, cfg, steps=steps, eos_id=eos_id,
                            paged=True)
    return jax.jit(loop, donate_argnums=(2,))


def jit_spec_decode_loop(model: Model, cfg: ServeConfig, *, steps: int,
                         eos_id: int | None):
    """Jitted self-speculative verify segment — the paged loop with
    ``speculate_k`` drafts per step; takes ``(..., history, pages)`` and
    returns ``emitted`` [steps, B, k+1] (PAD past each step's committed
    count).  Caches are donated as usual; the history array is tiny
    ([B, max_len] int32) and returned in the carry."""
    loop = make_decode_loop(model, cfg, steps=steps, eos_id=eos_id,
                            paged=True, speculate_k=cfg.speculate_k or 0)
    return jax.jit(loop, donate_argnums=(2,))


def make_join(model: Model, cfg: ServeConfig, *, eos_id: int | None):
    """Build the slot-refill step: batch-prefill the joining prompts (padded
    to one width) and select them into the live slot state.

    ``join_mask`` [B] picks the slots being (re)filled; rows outside the
    mask keep their caches, token, length and flags bit-for-bit (the
    prefill computes for every row, but ``jnp.where`` on the batch axis
    discards the non-joining rows).  Returns the refreshed state plus each
    row's first sampled token.
    """
    temp = cfg.temperature

    def join(params, caches, tok, lengths, done, remaining,
             join_mask, prompts, plens, budgets, key):
        with decode_attn_policy(mode=cfg.attn_mode,
                                interpret=cfg.attn_interpret):
            logits, new_caches = model.prefill(
                params, {"tokens": prompts}, cfg.max_len, dtype=cfg.dtype,
                last_pos=plens - 1)
        key, sub = jax.random.split(key)
        first = sample_tokens(logits[:, -1], sub, temp)
        if eos_id is None:
            is_eos = jnp.zeros_like(join_mask)
        else:
            is_eos = first == eos_id
        rem_new = budgets - 1
        tok = jnp.where(join_mask[:, None], first[:, None], tok)
        lengths = jnp.where(join_mask, plens, lengths)
        remaining = jnp.where(join_mask, rem_new, remaining)
        done = jnp.where(join_mask, is_eos | (rem_new <= 0), done)

        def select(new, old):
            m = join_mask.reshape((1, join_mask.shape[0])
                                  + (1,) * (new.ndim - 2))
            return jnp.where(m, new.astype(old.dtype), old)

        caches = jax.tree_util.tree_map(select, new_caches, caches)
        return caches, tok, lengths, done, remaining, key, first
    return join


def jit_join(model: Model, cfg: ServeConfig, *, eos_id: int | None):
    join = make_join(model, cfg, eos_id=eos_id)
    return jax.jit(join, donate_argnums=(1, 2, 3, 4, 5))


def paged_join_rows(joining: int) -> int:
    """Rows the paged join program computes for ``joining`` joining
    slots: whole groups of :data:`JOIN_GROUP_ROWS`."""
    return -(-joining // JOIN_GROUP_ROWS) * JOIN_GROUP_ROWS


def make_paged_join(model: Model, cfg: ServeConfig, *, eos_id: int | None):
    """Paged slot refill with a suffix-only prefill path that computes only
    the joining rows.  The slots in ``join_mask`` are gathered, in groups
    of :data:`JOIN_GROUP_ROWS` rows, into one ``lax.while_loop`` of
    ``[R, width]`` prefills; each group's results are scattered back to
    its slots.  Every other slot's state, and every page outside the
    joiners' tables, is bit-for-bit untouched; with no joiner no group
    runs and the state comes back as it went in.  The argument shapes are
    the full batch's, so one program per width serves any joining count.
    A group's padding rows (the last group's tail) get an all-sentinel
    page table and ``plens`` 1, so their scatters drop.  For *attention*
    segments the prefill *writes through the page table* into the one
    shared pooled allocation; SSM segments have per-slot recurrent state,
    not pages (init_paged_caches keeps them dense), so a group gathers
    its slots' state rows and scatters the fresh ones back.  ``pages`` is
    the full-width device page table.  The returned ``first`` is [B]:
    joining rows' sampled tokens, ``PAD_TOKEN`` elsewhere.

    Prefix sharing (repro.serve.prefixcache): ``prompts`` carries only
    each joining row's *uncached suffix* and ``prefix_lens`` [B] its
    cached-prefix depth (0 on a miss or with the cache off — then this is
    exactly the PR 2 full prefill).  The prefill runs at
    ``cache_len=prefix_lens``: suffix K/V scatters land at positions
    ``prefix_len + t`` (page-aligned prefixes mean the shared pages sit
    strictly below every write), RoPE continues at the absolute position,
    and the suffix queries attend *over the already-resident prefix pages*
    through the table gather — the prefix is neither recomputed nor
    restored.  A row may read a page that another row of the same join
    writes (a queue-mate matching pages a chunk or an admission of this
    round covers).  The writer writes at positions at or past its own
    ``prefix_len`` and the reader reads below its own, so the writer's
    ``prefix_len`` is strictly the smaller: the groups take the joining
    slots shallowest cached prefix first (ties in slot order), which puts
    every writer in the same group as its readers or an earlier one.
    Within a group per layer the pooled scatter precedes the gather, so
    every reader sees its writer's pages.

    Chunked prefill adds ``commit_mask`` [B]: the subset of joining rows
    whose prompt *completes* with this call.  Commit rows sample their
    first token and go live exactly as before.  Non-commit rows (a
    mid-prompt chunk) write their K/V and advance ``lengths`` to the new
    filled depth, but keep their token frozen, ``remaining`` at 0 and
    ``done`` latched True — the decode scan then treats them as retired
    slots (no sampling, no cache growth, PAD emissions) until a later
    join's chunk, at ``prefix_lens`` = the depth this one set, commits
    them.  With ``commit_mask == join_mask`` this is bit-for-bit the
    unchunked join.
    """
    from ..configs.base import BlockKind
    temp = cfg.temperature
    sentinel = cfg.pool_pages      # OOB page id (see kvpool.KVPool)
    seg_kinds = [s.kind for s in model.cfg.resolved_segments()]
    r = JOIN_GROUP_ROWS

    def join(params, caches, tok, lengths, done, remaining,
             join_mask, prompts, plens, budgets, key, pages, prefix_lens,
             commit_mask):
        b = join_mask.shape[0]
        count = join_mask.sum(dtype=jnp.int32)
        # joining slots first, shallowest cached prefix first (writers
        # before their readers), then the padding index b, whose
        # scatters drop; padded to whole groups
        depth = jnp.where(join_mask, prefix_lens, jnp.iinfo(jnp.int32).max)
        order = jnp.argsort(depth, stable=True).astype(jnp.int32)
        slots = jnp.where(jnp.arange(b) < count, order, b)
        slots = jnp.pad(slots, (0, -b % r), constant_values=b)

        def rows(a, idx, fill=0, axis=0):
            return jnp.take(a, idx, axis=axis, mode="fill", fill_value=fill)

        def body(carry):
            g, caches, tok, lengths, done, remaining, key, first = carry
            idx = jax.lax.dynamic_slice(slots, (g * r,), (r,))
            cache_len = rows(prefix_lens, idx)
            plen = rows(plens, idx, 1)
            # SSM leaves are [layers, B, ...]: their rows are on axis 1
            in_caches = [
                jax.tree_util.tree_map(lambda c: rows(c, idx, axis=1), oc)
                if kind is BlockKind.SSM else oc
                for kind, oc in zip(seg_kinds, caches)]
            with decode_attn_policy(mode=cfg.attn_mode,
                                    interpret=cfg.attn_interpret):
                logits, new_caches = model.prefill_paged(
                    params, {"tokens": rows(prompts, idx)}, in_caches,
                    rows(pages, idx, sentinel), dtype=cfg.dtype,
                    last_pos=plen - 1, cache_len=cache_len)
            caches = [
                jax.tree_util.tree_map(
                    lambda n, o: o.at[:, idx].set(n.astype(o.dtype),
                                                  mode="drop"), nc, oc)
                if kind is BlockKind.SSM else nc
                for kind, nc, oc in zip(seg_kinds, new_caches, caches)]
            key, sub = jax.random.split(key)
            f = sample_tokens(logits[:, -1], sub, temp)
            commit = rows(commit_mask, idx)      # False on padding rows
            is_eos = (jnp.zeros_like(commit) if eos_id is None
                      else f == eos_id)
            rem_new = rows(budgets, idx) - 1
            tok = tok.at[idx, 0].set(
                jnp.where(commit, f, rows(tok, idx)[:, 0]), mode="drop")
            lengths = lengths.at[idx].set(cache_len + plen, mode="drop")
            remaining = remaining.at[idx].set(
                jnp.where(commit, rem_new, 0), mode="drop")
            done = done.at[idx].set(
                jnp.where(commit, is_eos | (rem_new <= 0), True),
                mode="drop")
            first = first.at[idx].set(f, mode="drop")
            return g + 1, caches, tok, lengths, done, remaining, key, first

        groups = -(-count // r)
        first = jnp.full((b,), PAD_TOKEN, jnp.int32)
        carry = (jnp.int32(0), caches, tok, lengths, done, remaining, key,
                 first)
        (_, caches, tok, lengths, done, remaining, key,
         first) = jax.lax.while_loop(lambda c: c[0] < groups, body, carry)
        return caches, tok, lengths, done, remaining, key, first
    return join


def jit_paged_join(model: Model, cfg: ServeConfig, *, eos_id: int | None):
    join = make_paged_join(model, cfg, eos_id=eos_id)
    return jax.jit(join, donate_argnums=(1, 2, 3, 4, 5))

"""Optimistic admission + page-level preemption must be invisible in the
tokens: admitting on prompt pages only, growing on demand, and evicting
victims under pool pressure (recompute-on-resume through the ordinary
chunked-prefill join) produces bit-exact greedy output vs the
worst-case-reservation reference — while actually preempting, actually
packing more live slots into the same pool, and keeping every allocator /
radix invariant green at every scheduling round.

Covers the deterministic victim policy (priority classes, most-pages /
least-progress tie-breaks, the no-livelock barrier), config validation,
the chaos harness (forced exhaustion, victim override, simulated slot
failure), feature composition (chunked prefill x prefix cache x
speculation), the queue-wait/preemption latency satellite, and a
hypothesis stress test driving all of it against ``KVPool.check()`` /
``PrefixCache.check()`` with a no-preemption parity oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.chaos import ChaosInjector
from repro.serve.engine import ServeConfig
from repro.serve.scheduler import Batcher, _pct


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


BASE = dict(max_len=96, batch=6, dtype=jnp.float32, sync_every=4,
            page_size=8, total_pages=10,
            admission_mode="optimistic")


def _requests(cfg, n=9, lo=8, hi=14, seed=1):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, cfg.vocab,
                             size=int(rng.integers(lo, hi))).tolist())
            for i in range(n)]


def _run(model, params, requests, max_new=14, chaos=None,
         priorities=None, **kw):
    b = Batcher(model, params, ServeConfig(**{**BASE, **kw}), chaos=chaos)
    for rid, p in requests:
        b.submit(rid, p, priority=(priorities or {}).get(rid, 0))
    return b.run(max_new=max_new), b


def _reference(model, params, requests, max_new=14):
    """No-preemption oracle: worst-case reservation over an ample pool."""
    return _run(model, params, requests, max_new=max_new,
                admission_mode="reserve", total_pages=64)[0]


def _assert_parity(ref, got, requests):
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])


def _assert_drained(b):
    assert b.pool.used_pages == 0
    assert b.pool.preempted_pages == 0 or b.pool.free_pages >= 0
    b.pool.check()
    if b.prefix is not None:
        b.prefix.check()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_unknown_admission_mode_rejected(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="admission mode"):
        Batcher(model, params,
                ServeConfig(max_len=32, batch=2, admission_mode="eager"))


@pytest.mark.parametrize("kw", [{}, {"admission_mode": "optimistic"}],
                         ids=["plain", "optimistic"])
def test_dense_layout_rejected(kw):
    """The dense per-slot KV layout is gone: asking for it fails at
    configuration time, whatever else the configuration asks for."""
    with pytest.raises(ValueError, match="dense per-slot KV layout"):
        ServeConfig(max_len=32, batch=2, paged=False, **kw)


def test_optimistic_rejects_hybrid_ssm():
    """Preempting an SSM slot would discard a recurrent state recompute
    cannot rebuild from paged KV — rejected up front, before any cache
    is allocated (so no params are needed)."""
    model = Model(get_config("mamba2-370m").reduced())
    with pytest.raises(ValueError, match="attention-only"):
        Batcher(model, None,
                ServeConfig(max_len=32, batch=2,
                            admission_mode="optimistic"))


# ---------------------------------------------------------------------------
# victim policy (deterministic, synthetic pressure — no decode needed)
# ---------------------------------------------------------------------------

def _staged_batcher(model, params, **kw):
    """A live-looking slot table without running the model: reserve pages
    by hand and plant host bookkeeping the victim policy reads."""
    b = Batcher(model, params, ServeConfig(**{**BASE, "total_pages": 32,
                                              **kw}))
    return b


def _plant(b, slot, rid, tokens, outputs=0, priority=0, pending=False):
    b.pool.reserve(slot, tokens)
    b.slot_rid[slot] = rid
    b.slot_prompt[slot] = list(range(tokens))
    b.slot_len[slot] = tokens
    b.slot_filled[slot] = tokens
    b.slot_max_tokens[slot] = tokens + 16
    b.req_priority[rid] = priority
    if pending:
        b.slot_pending[slot] = [0] * 4
    if outputs:
        b.outputs[rid] = list(range(outputs))


def test_victim_lowest_priority_first(setup):
    cfg, model, params = setup
    b = _staged_batcher(model, params)
    _plant(b, 0, 10, tokens=32, outputs=1, priority=2)
    _plant(b, 1, 11, tokens=32, outputs=1, priority=0)
    _plant(b, 2, 12, tokens=32, outputs=1, priority=1)
    assert b._pick_victim() == 1


def test_victim_tiebreak_most_pages_then_least_progress(setup):
    cfg, model, params = setup
    b = _staged_batcher(model, params)
    _plant(b, 0, 10, tokens=16, outputs=1)       # 2 pages
    _plant(b, 1, 11, tokens=32, outputs=5)       # 4 pages, more progress
    _plant(b, 2, 12, tokens=32, outputs=1)       # 4 pages, less progress
    assert b._pick_victim() == 2                 # most pages, then least
    b.pool.release(2); b.slot_rid[2] = None      # progress breaks the tie
    assert b._pick_victim() == 1


def test_victim_prefilling_counts_as_zero_progress(setup):
    cfg, model, params = setup
    b = _staged_batcher(model, params)
    _plant(b, 0, 10, tokens=32, outputs=3)
    _plant(b, 1, 11, tokens=32, pending=True)    # PREFILLING: progress 0
    assert b._pick_victim() == 1


def test_victim_slot_id_breaks_final_tie(setup):
    cfg, model, params = setup
    b = _staged_batcher(model, params)
    _plant(b, 2, 12, tokens=16, outputs=2)
    _plant(b, 4, 14, tokens=16, outputs=2)
    assert b._pick_victim() == 2


def test_victim_barrier_protection_orders_last(setup):
    """A request preempted ``admission_max_skips`` times is protected:
    the policy only picks it when nothing unprotected is left — the
    no-livelock guarantee's policy half."""
    cfg, model, params = setup
    b = _staged_batcher(model, params, admission_max_skips=2)
    _plant(b, 0, 10, tokens=32, outputs=1)       # biggest, normally first
    _plant(b, 1, 11, tokens=16, outputs=5)
    b._preempt_counts[10] = 2                    # at the barrier bound
    assert b._pick_victim() == 1
    b.pool.release(1); b.slot_rid[1] = None
    assert b._pick_victim() == 0                 # sole candidate: allowed


def test_chaos_victim_override_wins_and_validates(setup):
    cfg, model, params = setup
    chaos = ChaosInjector(victim_override=lambda bat, cands: cands[-1])
    b = _staged_batcher(model, params)
    b.chaos = chaos
    _plant(b, 0, 10, tokens=32, outputs=1)
    _plant(b, 1, 11, tokens=16, outputs=5)
    assert b._pick_victim() == 1                 # override, not policy
    assert chaos.events[-1][1] == "victim_override"
    b.chaos = ChaosInjector(victim_override=lambda bat, cands: 5)
    with pytest.raises(ValueError, match="not in candidates"):
        b._pick_victim()


# ---------------------------------------------------------------------------
# end-to-end: overload -> preemption -> resume, bit-exact
# ---------------------------------------------------------------------------

def test_overload_preempts_resumes_and_matches_reference(setup):
    """The headline contract: a pool far too small for the worst case
    admits optimistically, preempts under genuine pressure, resumes via
    recompute, and the tokens are bit-identical to the no-preemption
    oracle — at strictly higher utilization and concurrency."""
    cfg, model, params = setup
    requests = _requests(cfg)
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests)
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0
    assert b.preempt_stats()["recomputed_ok"]
    assert b.preempted_token_recompute > 0
    _assert_drained(b)
    # same pool, reservation admission: strictly fewer live slots and
    # lower mean utilization (the capacity the tentpole reclaims)
    got_res, b_res = _run(model, params, requests,
                          admission_mode="reserve")
    _assert_parity(ref, got_res, requests)
    assert (b.kv_utilization()["peak_live_slots"]
            > b_res.kv_utilization()["peak_live_slots"])
    assert (b.kv_utilization()["mean_util"]
            > b_res.kv_utilization()["mean_util"])


def test_priority_class_survives_overload(setup):
    """Victims come from the low-priority class while it has members: the
    high-priority request is never preempted."""
    cfg, model, params = setup
    requests = _requests(cfg)
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests, priorities={3: 1})
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0
    assert 3 not in b.preempted_rids
    _assert_drained(b)


def test_preempted_request_completes_with_barrier(setup):
    """No-livelock, end to end: with the barrier bound at 1, the first
    preemption already protects the victim — it still completes, and is
    never evicted again while unprotected slots exist."""
    cfg, model, params = setup
    requests = _requests(cfg)
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests, admission_max_skips=1)
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0 and b.preempt_stats()["recomputed_ok"]
    assert not b._resumed and not b._preempt_counts
    _assert_drained(b)


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------

def test_chaos_forced_exhaustion_recovers_bit_exact(setup):
    cfg, model, params = setup
    requests = _requests(cfg)
    ref = _reference(model, params, requests)
    chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(8,),
                          check_invariants=True)
    got, b = _run(model, params, requests, chaos=chaos, total_pages=20)
    _assert_parity(ref, got, requests)
    assert b.preemptions >= 1
    assert any(kind == "hold" for _, kind, _ in chaos.events)
    assert any(kind == "release_held" for _, kind, _ in chaos.events)
    assert b.pool.held_pages == 0
    _assert_drained(b)


def test_chaos_slot_failure_mid_decode_recovers(setup):
    """A simulated device-state loss on the deepest live slot is handled
    as a preemption: the request recomputes and finishes bit-exact."""
    cfg, model, params = setup
    requests = _requests(cfg, n=5)
    ref = _reference(model, params, requests)
    chaos = ChaosInjector(fail_slot_at={3: "deepest"},
                          check_invariants=True)
    got, b = _run(model, params, requests, chaos=chaos, total_pages=24)
    _assert_parity(ref, got, requests)
    assert chaos.slot_failures == 1
    assert b.preempt_stats()["slot_failures"] == 1
    assert b.preemptions >= 1 and b.preempt_stats()["recomputed_ok"]
    _assert_drained(b)


def test_chaos_slot_failure_works_in_reserve_mode(setup):
    """Recovery does not depend on optimistic admission: a reserve-mode
    slot failure re-queues and re-reserves the worst case."""
    cfg, model, params = setup
    requests = _requests(cfg, n=4)
    ref = _reference(model, params, requests)
    chaos = ChaosInjector(fail_slot_at={2: "deepest"})
    got, b = _run(model, params, requests, chaos=chaos,
                  admission_mode="reserve", total_pages=24)
    _assert_parity(ref, got, requests)
    assert b.preemptions == 1
    _assert_drained(b)


# ---------------------------------------------------------------------------
# feature composition under pressure
# ---------------------------------------------------------------------------

def test_preemption_composes_with_chunked_prefill(setup):
    cfg, model, params = setup
    requests = _requests(cfg, n=7, lo=20, hi=34, seed=3)
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests, prefill_chunk=8,
                  total_pages=12)
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0 and b.chunk_joins > 0
    _assert_drained(b)


def test_preemption_composes_with_prefix_cache(setup):
    """Shared system prompt + pressure: preempted slots' registered pages
    park cached, resumes match their own history, and the radix tree
    stays consistent with the pool's partitions."""
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    system = rng.integers(0, cfg.vocab, size=16).tolist()
    requests = [(i, system + rng.integers(
        0, cfg.vocab, size=int(rng.integers(4, 10))).tolist())
        for i in range(8)]
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests, prefix_cache=True,
                  total_pages=12)
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0
    assert b.prefill_skipped > 0          # resumes/matches shortcut work
    _assert_drained(b)


def test_preemption_composes_with_speculation(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(6)
    tok = int(rng.integers(0, cfg.vocab))
    requests = [(i, [tok] * int(rng.integers(8, 14))) for i in range(8)]
    ref = _reference(model, params, requests)
    got, b = _run(model, params, requests, speculate_k=2,
                  total_pages=11)
    _assert_parity(ref, got, requests)
    assert b.preemptions > 0
    _assert_drained(b)


# ---------------------------------------------------------------------------
# latency / stats satellite
# ---------------------------------------------------------------------------

def test_pct_guards_empty_lists():
    assert _pct([], 50) == 0.0
    assert _pct([2.0], 95) == 2.0


def test_latency_stats_report_queue_wait_and_preemptions(setup):
    cfg, model, params = setup
    requests = _requests(cfg)
    _, b = _run(model, params, requests)
    lat = b.latency_stats()
    assert lat["preemptions"] == b.preemptions > 0
    assert lat["preempted_token_recompute"] > 0
    assert lat["queue_wait_p95_s"] >= lat["queue_wait_p50_s"] > 0.0
    # every admission (including re-admissions) closed a wait interval
    assert len(b.queue_waits) == len(b.admit_order)
    b.reset_stats()
    assert b.latency_stats()["queue_wait_p50_s"] == 0.0


# ---------------------------------------------------------------------------
# hypothesis stress: preemption x chunked x prefix x spec vs invariants
# ---------------------------------------------------------------------------

def test_stress_preemption_traffic_invariants(setup):
    """Random overloaded traffic with every feature armed and per-round
    invariant sweeps: bit-exact vs the no-preemption oracle, allocator
    and radix checks green at every scheduling round, pool fully drained,
    and every preempted request completed (no livelock).
    (importorskip inside the test, like the other serve suites, so the
    rest of this module still runs without hypothesis; ci.sh fails
    loudly when the install is missing.)"""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    cfg, model, params = setup

    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def inner(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**16),
                                              label="seed"))
        n_req = data.draw(st.integers(4, 9), label="n_req")
        system = rng.integers(
            0, cfg.vocab,
            size=data.draw(st.integers(0, 16), label="system")).tolist()
        requests = [(i, system + rng.integers(
            0, cfg.vocab, size=int(rng.integers(4, 14))).tolist())
            for i in range(n_req)]
        max_new = data.draw(st.integers(4, 14), label="max_new")
        pages = data.draw(st.integers(8, 14), label="pages")
        kw: dict = {"total_pages": pages}
        if data.draw(st.booleans(), label="chunked?"):
            kw["prefill_chunk"] = 8
        if data.draw(st.booleans(), label="prefix?"):
            kw["prefix_cache"] = True
        if data.draw(st.booleans(), label="spec?"):
            kw["speculate_k"] = 2
        priorities = {i: data.draw(st.integers(0, 1), label=f"prio{i}")
                      for i in range(n_req)}
        chaos = ChaosInjector(
            exhaust_at={data.draw(st.integers(2, 5), label="xr"): 0},
            release_at=(data.draw(st.integers(7, 10), label="rr"),),
            check_invariants=True)
        ref = _reference(model, params, requests, max_new=max_new)
        got, b = _run(model, params, requests, max_new=max_new,
                      chaos=chaos, priorities=priorities, **kw)
        _assert_parity(ref, got, requests)
        assert b.preempt_stats()["recomputed_ok"]
        assert not b._resumed
        assert b.pool.held_pages == 0
        _assert_drained(b)

    inner()

"""Hypothesis stress: overload protection composed with everything else.

Random overloaded traffic driving cancellation (expired deadlines),
SLO-burn/pressure degradation and shedding, page-level preemption,
prefix cache, chunked prefill and speculation — all at once, against
the allocator/radix invariant sweeps.  The schedule-independence
contract under test: **every request that completes emits tokens
bit-identical to the no-overload, no-pressure reference run**, every
request is accounted for exactly once (retired xor cancelled), and the
pool drains with nothing orphaned no matter which requests were
cancelled mid-flight.
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
from repro.serve.scheduler import Batcher


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


BASE = dict(max_len=96, batch=6, dtype=jnp.float32, sync_every=4,
            page_size=8, admission_mode="optimistic")


def test_stress_overload_traffic_invariants(setup):
    """Random traffic with deadlines, the degradation controller, chaos
    exhaustion and every serving feature armed: parity for completers,
    full accounting for everyone else, invariants green every round.
    (importorskip inside the test, like the other serve suites, so the
    rest of the module still runs without hypothesis; ci.sh fails
    loudly when the install is missing.)"""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    cfg, model, params = setup

    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def inner(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**16),
                                              label="seed"))
        n_req = data.draw(st.integers(5, 9), label="n_req")
        system = rng.integers(
            0, cfg.vocab,
            size=data.draw(st.integers(0, 16), label="system")).tolist()
        requests = [(i, system + rng.integers(
            0, cfg.vocab, size=int(rng.integers(4, 14))).tolist())
            for i in range(n_req)]
        max_new = data.draw(st.integers(4, 12), label="max_new")
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
        # a random subset carries an already-expired deadline (swept at
        # round one — a deterministic cancellation source) and another
        # subset a generous one that must always be met
        doomed = {i for i in range(n_req)
                  if data.draw(st.booleans(), label=f"doomed{i}")}
        chaos = ChaosInjector(
            exhaust_at={data.draw(st.integers(2, 5), label="xr"): 0},
            release_at=(data.draw(st.integers(7, 10), label="rr"),),
            check_invariants=True)

        def submit_all(b, with_deadlines):
            for rid, p in requests:
                dl = None
                if with_deadlines and rid in doomed:
                    dl = 0.0
                elif with_deadlines:
                    dl = 600.0
                b.submit(rid, p, priority=priorities[rid],
                         deadline_s=dl)

        # no-overload, no-pressure oracle: ample pool, reservation
        # admission, no controller, no deadlines
        ref_b = Batcher(model, params, ServeConfig(
            **{**BASE, **kw, "total_pages": 64,
               "admission_mode": "reserve"}))
        submit_all(ref_b, with_deadlines=False)
        ref = ref_b.run(max_new=max_new)

        b = Batcher(model, params, ServeConfig(
            **{**BASE, **kw, "overload": True,
               "overload_degrade_pressure": 0.5,
               "overload_shed_pressure": 0.9,
               "overload_up_rounds": 1, "overload_down_rounds": 2,
               "overload_queue_keep": data.draw(
                   st.integers(2, 6), label="keep")}), chaos=chaos)
        submit_all(b, with_deadlines=True)
        got = b.run(max_new=max_new)

        all_rids = {rid for rid, _ in requests}
        # exactly-once accounting: retired xor cancelled, nobody lost
        assert set(got) | set(b.cancelled) == all_rids
        assert set(got).isdisjoint(b.cancelled)
        # completers are bit-identical to the unloaded reference
        for rid in got:
            assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
        # an expired deadline can never be served
        for rid in doomed:
            assert rid not in got
            assert b.cancelled[rid] in ("deadline", "timeout")
        # deadline ledger: every completer carried a generous stamp and
        # met it; deadline/timeout cancels are scored misses; sheds are
        # excluded (RETRY_AFTER is an answer, not a late completion)
        st_ov = b.overload_stats()
        met, tot = st_ov["deadline_met"], st_ov["deadline_total"]
        dl_cancels = sum(1 for v in b.cancelled.values()
                         if v in ("deadline", "timeout"))
        assert met == len(got)
        assert tot == len(got) + dl_cancels
        # every preempted request was resolved (retired or cancelled)
        assert b.preempt_stats()["recomputed_ok"]
        assert not b._resumed
        # nothing orphaned: pool fully drained, invariants green
        assert b.pool.held_pages == 0
        assert b.pool.used_pages == 0
        b.pool.check()
        if b.prefix is not None:
            b.prefix.check()

    inner()

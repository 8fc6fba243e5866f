"""Overload protection: deadlines/cancellation, the degradation ladder,
and the progress watchdog must shed load *chosen, bounded, reversibly* —
and stay invisible in the tokens of every request that completes.

Covers the pure policy layer (``DegradationController`` hysteresis /
severity / time-in-state, ``project_finish_s`` abstention,
``Watchdog`` re-arm) with no model in the loop, then the scheduler's
actions: client/deadline/timeout cancellation from the queue and
mid-flight (pages released, allocator invariants green), RETRY_AFTER
shed rejections, deadline-attainment accounting at retire *and* cancel,
the CANCEL/DEGRADE/WATCHDOG trace events, the chaos ``stall_at`` /
``burst_at`` drills (watchdog trips, dumps the flight bundle via the
``$REPRO_FLIGHT_PATH`` override, and the run still finishes), and
bit-exact parity of completing requests under active degradation.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.chaos import ChaosInjector
from repro.serve.engine import ServeConfig
from repro.serve.overload import (DEGRADED, HEALTHY, RETRY_AFTER,
                                  SHEDDING, DegradationController,
                                  Watchdog, project_finish_s)
from repro.serve.scheduler import Batcher
from repro.serve.telemetry import MetricsRegistry


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


BASE = dict(max_len=96, batch=6, dtype=jnp.float32, sync_every=4,
            page_size=8, total_pages=24,
            admission_mode="optimistic")


def _requests(cfg, n=6, lo=8, hi=14, seed=1):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, cfg.vocab,
                             size=int(rng.integers(lo, hi))).tolist())
            for i in range(n)]


def _batcher(model, params, chaos=None, **kw):
    return Batcher(model, params, ServeConfig(**{**BASE, **kw}),
                   chaos=chaos)


# ---------------------------------------------------------------------------
# DegradationController: hysteresis state machine (pure host policy)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _observe_n(ctl, n, **sig):
    for r in range(n):
        ctl.observe(round=r, **sig)
    return ctl.state


def test_controller_climbs_and_descends_with_hysteresis():
    ctl = DegradationController(up_rounds=2, down_rounds=3)
    # one hot round is not enough; the cool round resets the streak
    ctl.observe(burn=1.5, pressure=0.0, queue_depth=0)
    ctl.observe(burn=0.0, pressure=0.0, queue_depth=0)
    ctl.observe(burn=1.5, pressure=0.0, queue_depth=0)
    assert ctl.state == HEALTHY
    assert _observe_n(ctl, 2, burn=1.5, pressure=0.0,
                      queue_depth=0) == DEGRADED
    # severity 2 climbs DEGRADED -> SHEDDING, again after up_rounds
    assert _observe_n(ctl, 2, burn=2.5, pressure=0.0,
                      queue_depth=0) == SHEDDING
    # recovery is deliberate: down_rounds per rung, two rungs down
    assert _observe_n(ctl, 3, burn=0.0, pressure=0.0,
                      queue_depth=0) == DEGRADED
    assert not ctl.recovered_to_healthy
    assert _observe_n(ctl, 3, burn=0.0, pressure=0.0,
                      queue_depth=0) == HEALTHY
    assert ctl.recovered_to_healthy
    assert [(f, t) for _, f, t, _, _ in ctl.transitions] == [
        (HEALTHY, DEGRADED), (DEGRADED, SHEDDING),
        (SHEDDING, DEGRADED), (DEGRADED, HEALTHY)]


def test_controller_severity_pressure_needs_queue_for_shed():
    ctl = DegradationController()
    # a full pool with an empty queue is not starvation: severity 1
    assert ctl.severity(burn=0.0, pressure=1.0, queue_depth=0) == 1
    assert ctl.severity(burn=0.0, pressure=1.0, queue_depth=3) == 2
    assert ctl.severity(burn=0.95, pressure=0.0, queue_depth=9) == 0


def test_controller_rung_properties():
    ctl = DegradationController(up_rounds=1)
    assert not (ctl.shed_speculation or ctl.shrink_chunk
                or ctl.freeze_growth or ctl.shedding)
    ctl.observe(burn=1.5, pressure=0.0, queue_depth=0)
    assert ctl.state == DEGRADED
    assert ctl.shed_speculation and ctl.shrink_chunk
    assert not ctl.freeze_growth and not ctl.shedding
    ctl.observe(burn=2.5, pressure=0.0, queue_depth=0)
    assert ctl.state == SHEDDING
    assert ctl.freeze_growth and ctl.shedding


def test_controller_time_in_state_and_reset():
    clk = FakeClock()
    ctl = DegradationController(up_rounds=1, clock=clk)
    clk.t = 5.0
    ctl.observe(burn=1.5, pressure=0.0, queue_depth=0)   # -> DEGRADED at 5
    clk.t = 7.0
    tis = ctl.stats()["time_in_state"]
    assert tis[HEALTHY] == pytest.approx(5.0)
    assert tis[DEGRADED] == pytest.approx(2.0)           # open interval
    ctl.reset()
    assert ctl.state == DEGRADED                         # rung survives
    assert ctl.stats()["time_in_state"][DEGRADED] == pytest.approx(0.0)
    assert not ctl.stats()["transitions"]
    assert not ctl.recovered_to_healthy


def test_controller_validates_thresholds():
    with pytest.raises(ValueError, match="rounds"):
        DegradationController(up_rounds=0)
    with pytest.raises(ValueError, match="degrade_burn"):
        DegradationController(degrade_burn=3.0, shed_burn=2.0)
    with pytest.raises(ValueError, match="degrade_pressure"):
        DegradationController(degrade_pressure=0.9, shed_pressure=0.5)


# ---------------------------------------------------------------------------
# project_finish_s: abstains without samples, optimistic with them
# ---------------------------------------------------------------------------

def test_projection_abstains_without_samples():
    m = MetricsRegistry()
    assert project_finish_s(m, 10, queued=True) is None
    assert project_finish_s(m, 10, queued=False) is None
    # a TTFT mean alone is enough for the queued estimate (decode term
    # falls back to zero — still optimistic, never pessimistic)
    m.observe("lat.ttft_s", 2.0)
    assert project_finish_s(m, 10, queued=True) == pytest.approx(2.0)
    assert project_finish_s(m, 10, queued=False) is None


def test_projection_uses_observed_means():
    m = MetricsRegistry()
    m.observe("lat.ttft_s", 1.0)
    m.observe("lat.ttft_s", 3.0)
    m.observe("lat.tpot_s", 0.5)
    assert project_finish_s(m, 5, queued=True) == pytest.approx(
        2.0 + 4 * 0.5)
    assert project_finish_s(m, 5, queued=False) == pytest.approx(2.5)
    assert project_finish_s(m, 0, queued=False) == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# Watchdog: trip once per `limit` unchanged rounds, then re-arm
# ---------------------------------------------------------------------------

def test_watchdog_trips_and_rearms():
    wd = Watchdog(limit=3)
    assert not wd.tick((1,))
    assert not wd.tick((2,))          # progress: counter resets
    assert not wd.tick((2,))
    assert not wd.tick((2,))
    assert wd.tick((2,))              # 3rd unchanged round: trip
    assert wd.trips == 1
    assert not wd.tick((2,))          # re-armed: counting again
    assert not wd.tick((2,))
    assert wd.tick((2,))
    assert wd.trips == 2
    with pytest.raises(ValueError, match=">= 1"):
        Watchdog(limit=0)


def test_watchdog_rounds_config_validated(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="watchdog"):
        _batcher(model, params, watchdog_rounds=0)


# ---------------------------------------------------------------------------
# cancellation: queue-side, mid-flight, reason codes, accounting
# ---------------------------------------------------------------------------

def test_client_cancel_queued_request(setup):
    cfg, model, params = setup
    b = _batcher(model, params)
    b.submit(0, [1, 2, 3])
    b.submit(1, [4, 5, 6])
    assert b.cancel(0)
    assert b.cancelled[0] == "client"
    assert [rid for rid, _ in b.queue] == [1]
    assert not b.cancel(0)            # already terminal
    assert not b.cancel(99)           # never submitted
    assert b.overload_stats()["cancelled_by_reason"]["client"] == 1


def test_cancel_rejects_unknown_reason(setup):
    cfg, model, params = setup
    b = _batcher(model, params)
    b.submit(0, [1, 2, 3])
    with pytest.raises(ValueError, match="cancel reason"):
        b.cancel(0, reason="bored")


def test_midflight_cancel_releases_pages(setup):
    """Plant a live-looking slot (the victim-policy test idiom) and
    cancel it: pages drain through ``_release_slot``, the device row is
    done-latched, and the allocator invariant stays green."""
    cfg, model, params = setup
    b = _batcher(model, params)
    b.pool.reserve(2, 32)
    b.slot_rid[2] = 7
    b.slot_prompt[2] = list(range(32))
    b.slot_len[2] = 32
    b.slot_filled[2] = 32
    b.slot_max_tokens[2] = 48
    used = b.pool.used_pages
    assert used > 0
    assert b.cancel(7)
    assert b.cancelled[7] == "client"
    assert b.slot_rid[2] is None
    assert b.pool.used_pages == 0
    assert bool(b.done[2])
    b.pool.check()


def test_deadline_zero_cancels_before_any_work(setup):
    """An already-expired deadline is swept at the first round: the
    request is a scored miss, unstamped peers are untouched."""
    cfg, model, params = setup
    b = _batcher(model, params)
    reqs = _requests(cfg, n=3)
    b.submit(reqs[0][0], reqs[0][1], deadline_s=0.0)
    for rid, p in reqs[1:]:
        b.submit(rid, p)
    results = b.run(max_new=4)
    assert b.cancelled[0] == "deadline"
    assert 0 not in results
    assert sorted(results) == [1, 2]
    st = b.overload_stats()
    assert st["deadline_total"] == 1 and st["deadline_met"] == 0
    assert st["deadline_attainment"] == 0.0
    b.pool.check()
    assert b.pool.used_pages == 0


def test_timeout_beats_deadline_as_reason(setup):
    cfg, model, params = setup
    b = _batcher(model, params)
    b.submit(0, [1, 2, 3, 4], deadline_s=0.0, timeout_s=0.0)
    b.submit(1, [5, 6, 7, 8])
    b.run(max_new=4)
    assert b.cancelled[0] == "timeout"
    # a timeout on a deadline-stamped request is still a scored miss
    assert b.overload_stats()["deadline_total"] == 1


def test_generous_deadlines_all_met(setup):
    cfg, model, params = setup
    b = _batcher(model, params)
    reqs = _requests(cfg, n=4)
    for rid, p in reqs:
        b.submit(rid, p, deadline_s=600.0)
    results = b.run(max_new=4)
    assert sorted(results) == [r for r, _ in reqs]
    st = b.overload_stats()
    assert st["deadline_total"] == 4 and st["deadline_met"] == 4
    assert st["deadline_attainment"] == 1.0
    assert not b.cancelled


def test_cancel_traced_and_perfetto_terminal(tmp_path, setup):
    """The CANCEL event lands on the rid's timeline with its reason, and
    the Perfetto export closes the queue span on it (a cancelled request
    is terminal, not a dangling open span)."""
    cfg, model, params = setup
    b = _batcher(model, params, telemetry=True)
    b.submit(0, [1, 2, 3], deadline_s=0.0)
    b.submit(1, [4, 5, 6])
    b.run(max_new=3)
    ev = [e for e in b.telemetry.timeline(0) if e["kind"] == "CANCEL"]
    assert len(ev) == 1 and ev[0]["reason"] == "deadline"
    out = tmp_path / "trace.json"
    b.telemetry.to_perfetto(str(out))
    data = json.loads(out.read_text())
    names = [(e.get("ph"), e.get("name")) for e in data["traceEvents"]]
    assert ("i", "CANCEL") in names
    # queue async span for rid 0 opened and closed
    q = [e["ph"] for e in data["traceEvents"]
         if e.get("id") == 0 and e["ph"] in ("b", "e")]
    assert q.count("b") == q.count("e") >= 1


def test_attribution_carries_cancel_reason(setup):
    """A cancelled-after-first-token request attributes like a retired
    one, with ``cancelled`` naming the reason (synthetic timeline — the
    attribution layer is pure arithmetic over the trace)."""
    from repro.serve.attribution import explain
    from repro.serve.telemetry import Tracer
    tr = Tracer()
    tr.event("SUBMIT", 5, t=0.0, prompt_tokens=3)
    tr.event("ADMIT", 5, t=0.5, slot=0)
    tr.event("FIRST_TOKEN", 5, t=1.0, slot=0, token=9, ttft_s=1.0)
    tr.event("CANCEL", 5, t=1.5, slot=0, reason="timeout")
    a = explain(tr, 5)
    assert a is not None and a.cancelled == "timeout"
    a.check()


# ---------------------------------------------------------------------------
# shedding: RETRY_AFTER ledger, priority order, resume protection
# ---------------------------------------------------------------------------

def test_shed_queued_lowest_priority_first_with_retry_after(setup):
    cfg, model, params = setup
    b = _batcher(model, params, overload=True, overload_queue_keep=2)
    for rid, prio in ((0, 1), (1, 0), (2, 0), (3, 2)):
        b.submit(rid, [1, 2, 3], priority=prio)
    b._resumed.add(2)                 # a paid-for resume: never shed
    b.overload.state = SHEDDING
    b._shed_queued()
    assert [rid for rid, _ in b.queue] == [2, 3]
    # the unprotected class-0 request goes first, then class-1; the
    # resumed class-0 request and the class-2 one survive
    assert set(b.cancelled) == {0, 1}
    assert all(v == "shed" for v in b.cancelled.values())
    st = b.overload_stats()
    assert st["shed_requests"] == 2
    assert [r["status"] for r in st["rejections"]] == [RETRY_AFTER] * 2
    assert all(r["retry_after_s"] > 0 for r in st["rejections"])
    # shed is excluded from the deadline ledger
    assert st["deadline_total"] == 0


# ---------------------------------------------------------------------------
# watchdog drill: chaos stall -> trip -> flight bundle -> force-shed
# ---------------------------------------------------------------------------

def test_watchdog_drill_sheds_and_dumps_bundle(tmp_path, setup,
                                               monkeypatch):
    """The deterministic livelock drill: a chaos ``stall_at`` freezes
    the round body past the watchdog bound.  The run must NOT raise —
    it dumps the flight bundle (via the $REPRO_FLIGHT_PATH env
    override), sheds the blocking head, and finishes what remains."""
    cfg, model, params = setup
    bundle = tmp_path / "stall_bundle.json"
    monkeypatch.setenv("REPRO_FLIGHT_PATH", str(bundle))
    reqs = _requests(cfg, n=5)
    chaos = ChaosInjector(stall_at={2: 12}, check_invariants=True)
    b = _batcher(model, params, chaos=chaos, watchdog_rounds=4)
    for rid, p in reqs:
        b.submit(rid, p)
    results = b.run(max_new=6)                 # must not raise
    st = b.overload_stats()
    assert st["watchdog_trips"] >= 1
    assert st["cancelled_by_reason"]["shed"] >= 1
    # every request is accounted for: retired or shed, none lost
    assert set(results) | set(b.cancelled) == {r for r, _ in reqs}
    assert set(results).isdisjoint(b.cancelled)
    # the bundle landed on disk through the env override and names the
    # stall (not a generic RuntimeError)
    data = json.loads(bundle.read_text())
    assert data["schema"] == 1
    assert "WatchdogStall" in data["error"]
    assert data["pool"] is not None
    assert b.last_flight_bundle["error"] == data["error"]
    assert any(kind == "stall" for _, kind, _ in chaos.events)
    b.pool.check()
    assert b.pool.used_pages == 0


def test_watchdog_survives_stall_shorter_than_limit(setup):
    """A stall shorter than the watchdog bound is absorbed: nothing is
    shed, every request completes."""
    cfg, model, params = setup
    reqs = _requests(cfg, n=3)
    chaos = ChaosInjector(stall_at={2: 3})
    b = _batcher(model, params, chaos=chaos, watchdog_rounds=10)
    for rid, p in reqs:
        b.submit(rid, p)
    results = b.run(max_new=4)
    assert sorted(results) == [r for r, _ in reqs]
    assert b.overload_stats()["watchdog_trips"] == 0
    assert not b.cancelled


# ---------------------------------------------------------------------------
# chaos burst: reproducible spike, deterministic synthetic prompts
# ---------------------------------------------------------------------------

def test_chaos_burst_is_deterministic(setup):
    cfg, model, params = setup

    def run_once():
        # round 1: the short wave drains in a single round, so the burst
        # rides the first round's admission alongside it
        chaos = ChaosInjector(burst_at={1: 3}, check_invariants=True)
        b = _batcher(model, params, chaos=chaos)
        for rid, p in _requests(cfg, n=3):
            b.submit(rid, p)
        return b.run(max_new=4), chaos

    r1, c1 = run_once()
    r2, c2 = run_once()
    burst_rids = [ChaosInjector.BURST_RID0 + i for i in range(3)]
    for rid in burst_rids:
        assert rid in r1 and r1[rid] == r2[rid]
    assert any(kind == "burst" for _, kind, _ in c1.events)
    assert c1.events == c2.events


# ---------------------------------------------------------------------------
# degradation parity: a degraded run changes scheduling, never tokens
# ---------------------------------------------------------------------------

def test_degraded_run_is_bit_exact_for_completing_requests(setup):
    """Force the ladder to DEGRADED from round one (degrade_pressure at
    the floor of the validation range) with speculation and chunking
    armed: both get shed/shrunk, yet every request completes with tokens
    identical to the unloaded reference."""
    cfg, model, params = setup
    rng = np.random.default_rng(6)
    tok = int(rng.integers(0, cfg.vocab))
    # more requests than slots: the drain spans several rounds, so the
    # controller observes nonzero pressure while work is still running
    # (a wave that retires inside round one never leaves HEALTHY)
    reqs = [(i, [tok] * int(rng.integers(10, 16))) for i in range(9)]

    def run_once(**kw):
        # short segments: pressure is observed at round top, so slots
        # must survive a round boundary for the controller to see them
        b = _batcher(model, params, speculate_k=2, prefill_chunk=16,
                     sync_every=2, **kw)
        for rid, p in reqs:
            b.submit(rid, p)
        return b.run(max_new=8), b

    ref, _ = run_once()
    got, b = run_once(overload=True, overload_degrade_pressure=0.01,
                      overload_shed_pressure=1.0, overload_up_rounds=1,
                      overload_down_rounds=50)
    assert got == ref
    st = b.overload_stats()["controller"]
    assert st["state"] != HEALTHY
    assert st["transitions"]
    assert not b.cancelled
    b.pool.check()
    assert b.pool.used_pages == 0


# ---------------------------------------------------------------------------
# stats surface
# ---------------------------------------------------------------------------

def test_stats_carry_overload_keys(setup):
    cfg, model, params = setup
    b = _batcher(model, params)
    for rid, p in _requests(cfg, n=2):
        b.submit(rid, p)
    b.run(max_new=3)
    lat = b.latency_stats()
    for k in ("cancellations", "shed_requests", "deadline_met",
              "deadline_total", "deadline_attainment", "watchdog_trips"):
        assert k in lat
    assert lat["deadline_attainment"] == 1.0   # vacuous without stamps
    st = b.overload_stats()
    assert st["enabled"] is False
    assert st["controller"]["state"] == HEALTHY
    assert set(st["controller"]["time_in_state"]) == {
        HEALTHY, DEGRADED, SHEDDING}


def test_reset_stats_clears_overload_ledgers(setup):
    cfg, model, params = setup
    b = _batcher(model, params, overload=True, overload_queue_keep=0)
    b.submit(0, [1, 2, 3])
    b.overload.state = SHEDDING
    b._shed_queued()
    assert b.overload_stats()["shed_requests"] == 1
    b.reset_stats()
    st = b.overload_stats()
    assert st["cancellations"] == 0 and st["shed_requests"] == 0
    assert not st["rejections"]
    assert not st["controller"]["transitions"]
    # the rung itself is live operational state, not a ledger
    assert b.overload.state == SHEDDING

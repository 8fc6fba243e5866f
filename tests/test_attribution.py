"""Bottleneck attribution, SLO monitor and flight recorder (PR 8).

The attribution contract is *exactness*: every request's TTFT and TPOT
decompositions must sum to the measured latency within float tolerance
(``RequestAttribution.check``), on plain runs and on the chaos run whose
preemptions exercise the recompute/requeue components.  The SLO monitor
must report deterministic attainment at generous/unmeetable targets and
stay vacuous when unconfigured.  The flight recorder must capture a
loadable debug bundle when a PageError escapes the run loop — with every
ring event at or before the failure round — while staying out of the
zero-overhead-off contract (no gauge wiring, no device syncs).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.attribution import (TPOT_COMPONENTS, TTFT_COMPONENTS,
                                     attribution_report, explain)
from repro.serve.chaos import ChaosInjector
from repro.serve.engine import ServeConfig
from repro.serve.kvpool import PageError
from repro.serve.scheduler import Batcher
from repro.serve.telemetry import Tracer


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


BASE = dict(max_len=96, batch=6, dtype=jnp.float32, sync_every=4,
            page_size=8, total_pages=10,
            admission_mode="optimistic")


def _requests(cfg, n=5, lo=8, hi=14, seed=1):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, cfg.vocab,
                             size=int(rng.integers(lo, hi))).tolist())
            for i in range(n)]


@pytest.fixture(scope="module")
def chaos_run(setup):
    """Traced preemption-heavy run: exhaustion at round 2, release at 5."""
    cfg, model, params = setup
    chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(5,),
                          check_invariants=True)
    b = Batcher(model, params, ServeConfig(**BASE, telemetry=True),
                chaos=chaos)
    for rid, p in _requests(cfg):
        b.submit(rid, p)
    results = b.run(max_new=10)
    return results, b


# ---------------------------------------------------------------------------
# per-request attribution: exact partitions
# ---------------------------------------------------------------------------

def test_explain_components_sum_to_measured(chaos_run):
    results, b = chaos_run
    tr = b.telemetry
    explained = 0
    for rid in results:
        a = explain(tr, rid)
        assert a is not None, f"rid {rid} produced tokens but no explain"
        a.check(tol=1e-6)            # exact-partition contract
        assert set(a.ttft) == set(TTFT_COMPONENTS)
        assert set(a.tpot) == set(TPOT_COMPONENTS)
        explained += 1
    assert explained == len(results)


def test_explain_components_nonnegative(chaos_run):
    _, b = chaos_run
    for rid in b.telemetry.rids():
        a = explain(b.telemetry, rid)
        for comp, v in {**a.ttft, **a.tpot}.items():
            assert v >= -1e-9, f"rid {rid} {comp} negative: {v}"


def test_explain_preempted_request_pays_recompute(chaos_run):
    # at least one preempted request must show queue/recompute cost
    # somewhere (the forced exhaustion parks it mid-flight)
    _, b = chaos_run
    preempted = {e["rid"] for e in b.telemetry.events
                 if e["kind"] == "PREEMPT"}
    assert preempted
    costs = []
    for rid in preempted:
        a = explain(b.telemetry, rid)
        assert a.preemptions >= 1
        costs.append(a.ttft["queue_wait_s"]
                     + a.ttft["preempt_recompute_s"]
                     + a.tpot["preempt_recompute_s"]
                     + a.tpot["requeue_s"])
    assert max(costs) > 0.0


def test_explain_unknown_rid_is_none(chaos_run):
    _, b = chaos_run
    assert explain(b.telemetry, 999_999) is None


def test_explain_spec_run_carves_verify_overhead(setup):
    cfg, model, params = setup
    b = Batcher(model, params,
                ServeConfig(max_len=96, batch=4, dtype=jnp.float32,
                            sync_every=4, page_size=8,
                            speculate_k=3, telemetry=True))
    tok = int(np.random.default_rng(0).integers(0, cfg.vocab))
    for rid in range(3):
        b.submit(rid, [tok] * 12)
    results = b.run(max_new=12)
    assert b.spec_steps > 0
    for rid in results:
        a = explain(b.telemetry, rid)
        a.check(tol=1e-6)
        # the drafter does not hit 100% acceptance on the whole run, so
        # some verify work was wasted — and it must stay a slice of (not
        # exceed) the decode-segment time it was carved from
        assert a.tpot["verify_overhead_s"] >= 0.0
        assert (a.tpot["verify_overhead_s"] + a.tpot["decode_segment_s"]
                <= a.decode_s + 1e-9)


# ---------------------------------------------------------------------------
# wave-level report
# ---------------------------------------------------------------------------

def test_attribution_report_shape_and_shares(chaos_run):
    results, b = chaos_run
    rep = attribution_report(b.telemetry)
    assert rep["requests"] == len(results)
    assert rep["dominant_ttft_component"] in TTFT_COMPONENTS
    assert rep["dominant_tpot_component"] in TPOT_COMPONENTS
    for section, comps in (("ttft", TTFT_COMPONENTS),
                           ("tpot", TPOT_COMPONENTS)):
        assert set(rep[section]) == set(comps)
        shares = sum(rep[section][c]["share"] for c in comps)
        assert shares == pytest.approx(1.0, abs=1e-6)
    # ranked: dominant component has the largest total
    dom = rep["dominant_ttft_component"]
    assert all(rep["ttft"][dom]["total_s"] >= rep["ttft"][c]["total_s"]
               for c in TTFT_COMPONENTS)
    # per-request entries sorted by descending TTFT, JSON-serializable
    ttfts = [r["ttft_s"] for r in rep["per_request"]]
    assert ttfts == sorted(ttfts, reverse=True)
    json.dumps(rep)


def test_attribution_report_empty_tracer():
    rep = attribution_report(Tracer())
    assert rep["requests"] == 0
    assert rep["dominant_ttft_component"] is None
    assert rep["per_request"] == []


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------

def test_slo_disabled_is_vacuous(chaos_run):
    _, b = chaos_run
    s = b.slo_stats()
    assert s["enabled"] is False
    assert s["slo_attainment"] == 1.0
    assert s["classes"] == {}


def _slo_run(setup, **slo_kw):
    cfg, model, params = setup
    b = Batcher(model, params, ServeConfig(**BASE, **slo_kw))
    for (rid, p), prio in zip(_requests(cfg, n=4), (0, 0, 1, 1)):
        b.submit(rid, p, priority=prio)
    b.run(max_new=6)
    return b


def test_slo_generous_attains_everything(setup):
    b = _slo_run(setup, ttft_slo_s=3600.0, tpot_slo_s=3600.0)
    s = b.slo_stats()
    assert s["enabled"] is True
    assert s["slo_attainment"] == 1.0
    assert set(s["classes"]) == {0, 1}
    for cls in s["classes"].values():
        assert cls["ttft_attainment"] == 1.0
        assert cls["ttft_total"] > 0
    assert s["burn_rate_ttft"] == 0.0
    assert s["burn_rate_tpot"] == 0.0


def test_slo_unmeetable_attains_nothing(setup):
    b = _slo_run(setup, ttft_slo_s=1e-12, tpot_slo_s=1e-12,
                 slo_target=0.9)
    s = b.slo_stats()
    assert s["slo_attainment"] == 0.0
    # every recent sample violates: burn = 1.0 / (1 - 0.9) = 10x budget
    assert s["burn_rate_ttft"] == pytest.approx(10.0)


def test_slo_counters_survive_in_registry(setup):
    b = _slo_run(setup, ttft_slo_s=3600.0)
    m = b.metrics
    total = sum(m.value(f"slo.ttft_total.c{c}") for c in (0, 1))
    assert total == m.count("lat.ttft_s") == 4


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class _PoolFault(ChaosInjector):
    """Raise a real allocator PageError at the first live round >= at."""

    def __init__(self, at=2):
        super().__init__()
        self.at = at
        self.fired = False

    def on_round(self, b):
        super().on_round(b)
        if not self.fired and b.round >= self.at and b.pool is not None:
            live = [i for i, rid in enumerate(b.slot_rid)
                    if rid is not None]
            if live:
                self.fired = True
                b.pool.reserve(live[0], 1)


def _crash_run(setup, tmp_path=None, **cfg_kw):
    cfg, model, params = setup
    path = str(tmp_path / "bundle.json") if tmp_path is not None else None
    b = Batcher(model, params,
                ServeConfig(**BASE, flight_path=path, **cfg_kw),
                chaos=_PoolFault())
    for rid, p in _requests(cfg, n=3):
        b.submit(rid, p)
    with pytest.raises(PageError):
        b.run(max_new=6)
    return b, path


def test_flight_bundle_on_page_error(setup, tmp_path):
    b, path = _crash_run(setup, tmp_path)
    bundle = b.last_flight_bundle
    assert bundle is not None
    assert bundle["schema"] == 1
    assert "PageError" in bundle["error"]
    assert bundle["events"], "ring captured nothing"
    # the ring holds the run *up to* the fault: nothing postdates it
    for e in bundle["events"]:
        assert e["round"] <= bundle["round"]
    # pool snapshot partitions cover every page exactly once
    pool = bundle["pool"]
    covered = (len(pool["free"]) + len(pool["cached"])
               + len(pool["preempted"]) + len(pool["held"])
               + sum(len(p) for p in pool["slot_pages"]))
    assert covered == pool["n_pages"]
    assert len(bundle["slot_table"]["slot_rid"]) == BASE["batch"]
    # the on-disk bundle is the same loadable JSON
    with open(path) as f:
        disk = json.load(f)
    assert disk["error"] == bundle["error"]
    assert disk["round"] == bundle["round"]
    json.dumps(bundle)


def test_flight_recorder_ring_is_bounded(setup, tmp_path):
    b, _ = _crash_run(setup, tmp_path, flight_events=4)
    assert len(b.last_flight_bundle["events"]) <= 4


def test_flight_recorder_opt_out(setup):
    b, _ = _crash_run(setup, flight_recorder=False)
    assert b.flight is None
    assert b.last_flight_bundle is None


def test_flight_recorder_does_not_break_off_contract(setup):
    # always-on flight ring must not wire gauges or perturb tokens:
    # the zero-overhead-off tests in test_telemetry cover parity; here
    # just pin the wiring invariants on a default Batcher
    cfg, model, params = setup
    b = Batcher(model, params, ServeConfig(**BASE))
    assert b.telemetry is None
    assert b.flight is not None          # recorder armed by default
    assert b.pool.gauge_cb is None       # but no per-mutation callback


def test_tracer_ring_mode_keeps_tail():
    tr = Tracer(ring=3)
    for i in range(10):
        tr.event("SUBMIT", i, round=i)
    tail = tr.tail()
    assert len(tail) == 3
    assert [e["rid"] for e in tail] == [7, 8, 9]

"""Unified serve telemetry: the Tracer's lifecycle stream must be
complete (every submitted rid runs SUBMIT -> ... -> RETIRE with
monotone rounds, preemptions show PREEMPT -> ADMIT -> RESUME), the
Perfetto export must be schema-valid trace_event JSON with
non-overlapping slot spans, and the MetricsRegistry must reproduce the
legacy ``*_stats()`` numbers bit-for-bit while ``reset_stats()`` now
clears *everything* it accumulates.  Also covers the chaos-fault trace,
the pool-partition gauge, the kernel timing hooks, and the
zero-overhead-off contract (no tracer calls reachable when telemetry is
off).
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
from repro.serve.scheduler import Batcher
from repro.serve.telemetry import (CHAOS_KINDS, LIFECYCLE_KINDS,
                                   MetricsRegistry, Tracer, _pct)


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


def _chaos_run(setup, max_new=10, **kw):
    """The canonical traced chaos run: forced exhaustion at round 2,
    release at round 5 — guarantees preemption at these sizes."""
    cfg, model, params = setup
    chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(5,),
                          check_invariants=True)
    b = Batcher(model, params,
                ServeConfig(**{**BASE, **kw}, telemetry=True), chaos=chaos)
    for rid, p in _requests(cfg):
        b.submit(rid, p)
    results = b.run(max_new=max_new)
    return results, b


@pytest.fixture(scope="module")
def chaos_run(setup):
    return _chaos_run(setup)


# ---------------------------------------------------------------------------
# MetricsRegistry units
# ---------------------------------------------------------------------------

def test_registry_counters_gauges():
    m = MetricsRegistry()
    m.inc("a.b")
    m.inc("a.b", 4)
    assert m.value("a.b") == 5
    assert m.value("missing") == 0
    m.set_gauge("pool.free_pages", 7)
    assert m.gauge("pool.free_pages") == 7
    assert m.gauge("missing", -1) == -1


def test_registry_histogram_keeps_raw_samples():
    m = MetricsRegistry()
    for v in (0.3, 1.0, 0.01):
        m.observe("lat.x_s", v)
    assert m.count("lat.x_s") == 3
    assert m.sum("lat.x_s") == pytest.approx(1.31)
    # percentile must be the legacy _pct over the raw list, not a
    # bucket-interpolated estimate
    assert m.percentile("lat.x_s", 50) == _pct([0.3, 1.0, 0.01], 50)
    assert m.percentile("empty", 95) == 0.0
    # bucket counts track the same observations
    assert sum(m.hist("lat.x_s").counts) == 3


def test_registry_histogram_caps_reservoir():
    # the raw-sample reservoir is bounded: running count/sum stay exact
    # while the kept samples decimate deterministically past the cap
    from repro.serve.telemetry import _Histogram
    h = _Histogram(cap=64)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert h.count == n
    assert h.sum == pytest.approx(sum(range(n)))
    assert len(h.samples) <= 64
    # decimation is stride-based, so the survivors still span the range
    assert min(h.samples) < n * 0.1 and max(h.samples) > n * 0.8
    h.reset()
    assert h.count == 0 and h.sum == 0.0 and h.samples == []
    # percentiles over a capped registry hist remain order-of-magnitude
    # right (survivors are an evenly-strided subsample)
    m = MetricsRegistry()
    for i in range(n):
        m.observe("lat.x_s", float(i))
    assert m.count("lat.x_s") == n
    assert m.percentile("lat.x_s", 50) == pytest.approx(n / 2, rel=0.2)


def test_registry_reset_clears_counters_and_hists_keeps_gauges():
    m = MetricsRegistry()
    m.inc("c", 3)
    m.observe("h", 1.0)
    m.set_gauge("g", 2)
    m.reset()
    assert m.value("c") == 0
    assert m.count("h") == 0 and m.samples("h") == []
    assert m.gauge("g") == 2          # gauges describe current state


def test_registry_reset_gauges_opt_in():
    m = MetricsRegistry()
    m.set_gauge("pool.free_pages", 7)
    m.set_gauge("other.g", 1)
    m.clear_gauges("pool.")
    assert m.gauge("pool.free_pages", -1) == -1
    assert m.gauge("other.g") == 1
    m.reset(gauges=True)
    assert m.gauge("other.g", -1) == -1


def test_registry_snapshot_flat():
    m = MetricsRegistry()
    m.inc("spec.steps", 2)
    m.observe("lat.ttft_s", 0.5)
    m.set_gauge("pool.free_pages", 3)
    s = m.snapshot()
    assert s["spec.steps"] == 2
    assert s["pool.free_pages"] == 3
    assert s["lat.ttft_s.count"] == 1
    assert s["lat.ttft_s.p50"] == 0.5


# ---------------------------------------------------------------------------
# Tracer units
# ---------------------------------------------------------------------------

def test_tracer_timeline_sorted_and_copied():
    clock = iter(float(i) for i in range(100))
    tr = Tracer(clock=lambda: next(clock))
    tr.event("SUBMIT", 1, round=0)
    tr.event("ADMIT", 1, round=1, slot=0)
    tr.event("SUBMIT", 2, round=1)
    tl = tr.timeline(1)
    assert [e["kind"] for e in tl] == ["SUBMIT", "ADMIT"]
    tl[0]["kind"] = "corrupted"
    assert tr.events[0]["kind"] == "SUBMIT"      # copies, not aliases
    assert tr.rids() == [1, 2]


def test_tracer_span_contextmanager():
    ts = iter([0.0, 1.0, 3.0])
    tr = Tracer(clock=lambda: next(ts))
    with tr.span("join", round=4):
        pass
    (sp,) = tr.spans
    assert sp == {"name": "join", "round": 4, "t0": 1.0, "t1": 3.0}


# ---------------------------------------------------------------------------
# trace completeness on the real scheduler
# ---------------------------------------------------------------------------

def test_trace_complete_lifecycles(chaos_run):
    results, b = chaos_run
    tr = b.telemetry
    assert tr is not None
    rids = set(tr.rids()) - {None}
    assert rids == set(results)          # every request left a trace
    for rid in rids:
        tl = tr.timeline(rid)
        kinds = [e["kind"] for e in tl]
        assert kinds[0] == "SUBMIT"
        assert kinds[-1] == "RETIRE"
        assert kinds.count("RETIRE") == 1
        assert "FIRST_TOKEN" in kinds
        rounds = [e["round"] for e in tl]
        assert rounds == sorted(rounds), (rid, kinds, rounds)
        for e in tl:
            assert e["kind"] in LIFECYCLE_KINDS
            assert e["pool_free"] >= 0 and e["pages_held"] >= 0


def test_trace_preempt_resume_pairs(chaos_run):
    _, b = chaos_run
    tr = b.telemetry
    assert b.preemptions > 0             # the chaos run actually preempted
    preempted = [rid for rid in tr.rids()
                 if any(e["kind"] == "PREEMPT" for e in tr.timeline(rid))]
    assert preempted
    total_preempts = 0
    for rid in preempted:
        tl = tr.timeline(rid)
        kinds = [e["kind"] for e in tl]
        total_preempts += kinds.count("PREEMPT")
        # every PREEMPT is followed by a re-ADMIT then RESUME (or the
        # request retired… which cannot happen: recompute always resumes)
        for i, k in enumerate(kinds):
            if k == "PREEMPT":
                rest = kinds[i + 1:]
                assert "ADMIT" in rest and "RESUME" in rest, (rid, kinds)
                assert rest.index("ADMIT") < rest.index("RESUME")
        # a preempted rid's RESUME carries its prior decode progress
        resumes = [e for e in tl if e["kind"] == "RESUME"]
        assert all(e["prior_tokens"] >= 0 for e in resumes)
    assert total_preempts == b.preemptions


def test_trace_preempt_rid_moves_or_reuses_slot(chaos_run):
    _, b = chaos_run
    tr = b.telemetry
    for rid in tr.rids():
        tl = tr.timeline(rid)
        admits = [e for e in tl if e["kind"] == "ADMIT"]
        preempts = [e for e in tl if e["kind"] == "PREEMPT"]
        # one ADMIT per admission: initial + one per preemption
        assert len(admits) == 1 + len(preempts)
        for e in admits + preempts:
            assert e["slot"] is not None


def test_chaos_faults_land_in_trace(chaos_run):
    _, b = chaos_run
    tr = b.telemetry
    kinds = {e["kind"] for e in tr.events if e["rid"] is None}
    assert "CHAOS_HOLD" in kinds
    assert "CHAOS_RELEASE_HELD" in kinds
    assert kinds <= set(CHAOS_KINDS)
    hold = next(e for e in tr.events if e["kind"] == "CHAOS_HOLD")
    # pages may be 0 when the free list was already drained at round 2 —
    # the event recording the (attempted) raid is what matters
    assert hold["round"] == 2 and hold["pages"] >= 0
    assert hold["keep_free"] == 0


def test_pool_gauge_sampled(chaos_run):
    _, b = chaos_run
    tr = b.telemetry
    assert tr.pool_samples
    for _, counts in tr.pool_samples:
        assert set(counts) == {"free", "mapped", "cached", "preempted",
                               "held"}
        assert sum(counts.values()) == b.pool.n_pages
    # registry mirrors the last sample
    assert b.metrics.gauge("pool.free_pages") == tr.pool_samples[-1][1]["free"]


def test_scheduler_spans_per_round(chaos_run):
    _, b = chaos_run
    tr = b.telemetry
    names = {sp["name"] for sp in tr.spans}
    assert {"join", "decode-segment", "collect", "chaos"} <= names
    for sp in tr.spans:
        assert sp["t1"] >= sp["t0"]


# ---------------------------------------------------------------------------
# Perfetto export schema
# ---------------------------------------------------------------------------

def test_perfetto_schema_valid(chaos_run, tmp_path):
    _, b = chaos_run
    path = tmp_path / "trace.json"
    data = b.telemetry.to_perfetto(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == data
    evs = loaded["traceEvents"]
    assert evs and loaded["displayTimeUnit"] == "ms"
    valid_ph = {"M", "X", "i", "C", "b", "e"}
    for e in evs:
        assert e["ph"] in valid_ph, e
        assert e["pid"] == 1
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")
        if e["ph"] in ("b", "e"):
            assert "id" in e
    # process/thread metadata present for every tid used
    tids_used = {e["tid"] for e in evs if "tid" in e and e["ph"] != "M"}
    tids_named = {e["tid"] for e in evs
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids_used <= tids_named


def test_perfetto_slot_spans_never_overlap(chaos_run):
    _, b = chaos_run
    evs = b.telemetry.to_perfetto()["traceEvents"]
    by_tid: dict = {}
    for e in evs:
        if e["ph"] == "X" and e.get("cat") == "slot":
            by_tid.setdefault(e["tid"], []).append(e)
    assert by_tid                       # at least one slot track
    for tid, spans in by_tid.items():
        spans.sort(key=lambda e: e["ts"])
        for a, bsp in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= bsp["ts"] + 1e-6, (tid, a, bsp)


def test_perfetto_preempted_span_ends_with_preempt(chaos_run):
    _, b = chaos_run
    evs = b.telemetry.to_perfetto()["traceEvents"]
    slot_spans = [e for e in evs
                  if e["ph"] == "X" and e.get("cat") == "slot"]
    ended = {e["args"]["end"] for e in slot_spans}
    assert "PREEMPT" in ended and "RETIRE" in ended
    # the preempted rid re-appears in a later span (same or other slot)
    pre = next(e for e in slot_spans if e["args"]["end"] == "PREEMPT")
    rid = pre["args"]["rid"]
    later = [e for e in slot_spans
             if e["args"]["rid"] == rid and e["ts"] >= pre["ts"] + pre["dur"]]
    assert later and any(e["args"]["end"] == "RETIRE" for e in later)


def test_perfetto_queue_spans_balanced(chaos_run):
    _, b = chaos_run
    evs = b.telemetry.to_perfetto()["traceEvents"]
    opens = [e["id"] for e in evs if e["ph"] == "b"]
    closes = [e["id"] for e in evs if e["ph"] == "e"]
    assert sorted(opens) == sorted(closes)   # every queue span closed
    assert opens                             # and some existed


def test_perfetto_spec_commits_on_slot_tracks(setup):
    # speculation under trace: SPEC_COMMIT instants land on the slot
    # track of the committing slot with their accepted counts, and each
    # request's FIRST_TOKEN precedes its first SPEC_COMMIT (a draft can
    # only verify against an already-started decode)
    from repro.serve.telemetry import _TID_SLOT0
    cfg, model, params = setup
    b = Batcher(model, params,
                ServeConfig(max_len=96, batch=4, dtype=jnp.float32,
                            sync_every=4, page_size=8,
                            speculate_k=3, telemetry=True))
    tok = int(np.random.default_rng(0).integers(0, cfg.vocab))
    for rid in range(3):
        b.submit(rid, [tok] * 12)
    b.run(max_new=12)
    commits = [e for e in b.telemetry.events if e["kind"] == "SPEC_COMMIT"]
    assert commits
    evs = b.telemetry.to_perfetto()["traceEvents"]
    marks = [e for e in evs if e["ph"] == "i" and e["name"] == "SPEC_COMMIT"]
    assert len(marks) == len(commits)
    for e in marks:
        slot = e["args"]["slot"]
        assert e["tid"] == _TID_SLOT0 + slot     # rides its slot's track
        assert e["args"]["accepted_drafts"] >= 0
        assert e["args"]["committed"] >= 1       # every step commits >= 1
    for rid in range(3):
        tl = b.telemetry.timeline(rid)
        kinds = [e["kind"] for e in tl]
        assert "FIRST_TOKEN" in kinds and "SPEC_COMMIT" in kinds
        assert kinds.index("FIRST_TOKEN") < kinds.index("SPEC_COMMIT")
        first = next(e for e in tl if e["kind"] == "FIRST_TOKEN")
        commit = next(e for e in tl if e["kind"] == "SPEC_COMMIT")
        assert first["t"] <= commit["t"]


# ---------------------------------------------------------------------------
# metrics vs legacy stats equivalence + reset
# ---------------------------------------------------------------------------

def test_metrics_match_legacy_stats(chaos_run):
    _, b = chaos_run
    m = b.metrics
    lat = b.latency_stats()
    assert lat["ttft_p50_s"] == _pct(b.ttfts, 50)
    assert lat["ttft_p95_s"] == m.percentile("lat.ttft_s", 95)
    assert lat["tpot_p50_s"] == m.percentile("lat.tpot_s", 50)
    assert lat["queue_wait_p95_s"] == m.percentile("lat.queue_wait_s", 95)
    assert lat["preemptions"] == m.value("preempt.count") == b.preemptions
    assert lat["requests"] == m.count("lat.ttft_s")
    k = b.preempt_stats()
    assert k["preemptions"] == m.value("preempt.count")
    assert k["recompute_tokens"] == m.value("preempt.recompute_tokens")
    j = b.join_stats()
    assert j["joins"] == m.count("join.seconds")
    assert j["max_join_s"] == (max(m.samples("join.seconds"))
                               if m.count("join.seconds") else 0.0)
    p = b.prefix_stats()
    assert p["prefill_computed"] == m.value("prefill.computed_tokens")
    assert p["prefill_skipped"] == m.value("prefill.skipped_tokens")


def test_spec_metrics_match_legacy(setup):
    cfg, model, params = setup
    b = Batcher(model, params,
                ServeConfig(max_len=96, batch=4, dtype=jnp.float32,
                            sync_every=4, page_size=8,
                            speculate_k=3, telemetry=True))
    tok = int(np.random.default_rng(0).integers(0, cfg.vocab))
    for rid in range(3):
        b.submit(rid, [tok] * 12)
    b.run(max_new=12)
    m = b.metrics
    s = b.spec_stats()
    assert b.spec_steps == m.value("spec.steps") > 0
    assert b.spec_accepted == m.value("spec.accepted")
    assert s["acceptance_rate"] == pytest.approx(
        m.value("spec.accepted") / max(1, m.value("spec.proposed")))
    # SPEC_COMMIT events carry the same totals as the counters
    commits = [e for e in b.telemetry.events if e["kind"] == "SPEC_COMMIT"]
    assert sum(e["committed"] for e in commits) == b.spec_emitted
    assert sum(e["accepted_drafts"] for e in commits) == b.spec_accepted


def test_reset_stats_clears_everything(setup):
    results, b = _chaos_run(setup)
    assert b.preemptions > 0 and b.ttfts and b.queue_waits
    b.kv_samples = [0.5]
    b.reset_stats()
    assert b.ttfts == [] and b.tpots == [] and b.queue_waits == []
    assert b.join_times == [] and b.kv_samples == []
    assert b.preemptions == 0 and b.preempted_token_recompute == 0
    assert b.prefill_computed == 0 and b.prefill_skipped == 0
    assert b.spec_steps == 0 and b.chunk_joins == 0
    assert b.budget_deferrals == 0
    assert not b._first_tok_t
    assert b.preempt_events == [] and b.preempted_rids == set()
    assert b.latency_stats()["ttft_p50_s"] == 0.0
    assert b.join_stats()["joins"] == 0


# ---------------------------------------------------------------------------
# zero-overhead-off contract
# ---------------------------------------------------------------------------

def test_telemetry_off_by_default(setup):
    cfg, model, params = setup
    b = Batcher(model, params, ServeConfig(**BASE))
    assert b.telemetry is None
    assert b.pool.gauge_cb is None       # no per-mutation callback wired
    for rid, p in _requests(cfg, n=2):
        b.submit(rid, p)
    results = b.run(max_new=4)
    assert all(len(v) == 4 for v in results.values())
    # metrics still accumulate (they are the *_stats substrate)
    assert b.metrics.count("lat.ttft_s") == 2


def test_traced_off_equals_untraced_tokens(setup):
    # tracing must observe, not perturb: same greedy tokens either way
    res_on, _ = _chaos_run(setup)
    cfg, model, params = setup
    chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(5,),
                          check_invariants=True)
    b = Batcher(model, params, ServeConfig(**BASE), chaos=chaos)
    for rid, p in _requests(cfg):
        b.submit(rid, p)
    res_off = b.run(max_new=10)
    assert res_on == res_off


# ---------------------------------------------------------------------------
# kernel timing hooks
# ---------------------------------------------------------------------------

def test_kernel_hooks_off_record_nothing():
    from repro.kernels.paged_attn import attn_telemetry, paged_attn
    tel = attn_telemetry()
    tel.disable()
    tel.reset()
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(1, 4, 2, 4, 8)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
    tbl = jnp.zeros((2, 2), jnp.int32)
    ln = jnp.asarray([3, 5], jnp.int32)
    paged_attn(q, kp, kp, tbl, ln)
    assert tel.stats == {}


def test_kernel_hooks_record_ops_routes():
    from repro.kernels.paged_attn import (attn_telemetry, paged_attn,
                                          paged_attn_xla,
                                          paged_prefill_attn,
                                          paged_verify_attn)
    tel = attn_telemetry()
    tel.reset()
    tel.enable()
    try:
        rng = np.random.default_rng(0)
        kp = jnp.asarray(rng.normal(size=(1, 6, 2, 4, 8)), jnp.float32)
        tbl = jnp.asarray(rng.integers(0, 6, size=(2, 3)), jnp.int32)
        ln = jnp.asarray([5, 9], jnp.int32)
        q1 = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
        q3 = jnp.asarray(rng.normal(size=(2, 3, 4, 8)), jnp.float32)
        paged_attn(q1, kp, kp, tbl, ln)
        paged_attn_xla(q1, kp, kp, tbl, ln)
        paged_prefill_attn(q3, kp, kp, tbl, ln - 3, ln)
        paged_verify_attn(q3, kp, kp, tbl, ln, ln)
        snap = tel.snapshot()
        assert snap["decode.kernel"]["calls"] == 1
        assert snap["decode.kernel"]["tokens"] == 2       # B=2, Lq=1
        assert snap["decode.xla"]["calls"] == 1
        ops = {k.split(".")[0] for k in snap}
        assert {"decode", "prefill", "verify"} <= ops
        # eager calls are timed; none were traced
        for v in snap.values():
            assert v["traced_calls"] == 0 and v["wall_s"] > 0.0
    finally:
        tel.disable()
        tel.reset()


def test_kernel_hooks_traced_counted_not_timed():
    from repro.kernels.paged_attn import attn_telemetry, paged_prefill_attn
    tel = attn_telemetry()
    tel.reset()
    tel.enable()
    try:
        rng = np.random.default_rng(0)
        kp = jnp.asarray(rng.normal(size=(1, 6, 2, 4, 8)), jnp.float32)
        tbl = jnp.asarray(rng.integers(0, 6, size=(2, 3)), jnp.int32)
        ln = jnp.asarray([5, 9], jnp.int32)
        q3 = jnp.asarray(rng.normal(size=(2, 3, 4, 8)), jnp.float32)
        f = jax.jit(lambda q: paged_prefill_attn(q, kp, kp, tbl,
                                                 ln - 3, ln))
        f(q3).block_until_ready()
        f(q3).block_until_ready()        # compile cache: no re-trace
        snap = tel.snapshot()
        (row,) = snap.values()
        assert row["calls"] == row["traced_calls"] == 1
        assert row["wall_s"] == 0.0      # never timed under trace
        # traced calls still contribute analytic traffic (full sliced
        # table assumed live) but no timed bytes -> no achieved GB/s
        assert row["bytes"] > 0.0 and row["flops"] > 0.0
        assert row["timed_bytes"] == 0.0
        assert row["achieved_gbps"] == 0.0
    finally:
        tel.disable()
        tel.reset()


def test_kernel_roofline_all_ops_on_kernel_route():
    # acceptance: nonzero achieved GB/s and op/byte for decode, prefill
    # and verify on the *kernel* route (policy-forced, interpret mode)
    from repro.kernels.decode_attn import decode_attn_policy
    from repro.kernels.paged_attn import (amenability_reports,
                                          attn_telemetry, paged_attn,
                                          paged_prefill_attn,
                                          paged_verify_attn)
    tel = attn_telemetry()
    tel.reset()
    tel.enable()
    try:
        rng = np.random.default_rng(0)
        kp = jnp.asarray(rng.normal(size=(1, 6, 2, 4, 8)), jnp.float32)
        tbl = jnp.asarray(rng.integers(0, 6, size=(2, 3)), jnp.int32)
        ln = jnp.asarray([5, 9], jnp.int32)
        q1 = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
        q3 = jnp.asarray(rng.normal(size=(2, 3, 4, 8)), jnp.float32)
        with decode_attn_policy(mode="kernel", interpret=True):
            paged_attn(q1, kp, kp, tbl, ln, interpret=True)
            paged_prefill_attn(q3, kp, kp, tbl, ln - 3, ln)
            paged_verify_attn(q3, kp, kp, tbl, ln, ln)
        snap = tel.snapshot()
        for op in ("decode", "prefill", "verify"):
            row = snap[f"{op}.kernel"]
            assert row["achieved_gbps"] > 0.0, (op, row)
            assert row["op_byte"] > 0.0, (op, row)
            assert row["timed_bytes"] == row["bytes"] > 0.0
        # dead-page subtraction: slot 0 (5 live tokens, page_size 4)
        # touches 2 of its 3 table pages in decode, slot 1 all 3 — the
        # K+V page traffic must reflect 5 live pages, not 6
        page_bytes = 4 * 2 * 8 * 4 * 2            # ps*Hkv*D*itemsize*(K+V)
        q_bytes = 2 * 2 * 4 * 8 * 4               # Q read + O write
        tbl_bytes = 2 * 3 * 4
        assert snap["decode.kernel"]["bytes"] == pytest.approx(
            5 * page_bytes + q_bytes + tbl_bytes)
        # attention is memory-bound at these shapes: the paper's test
        # must judge every measured op bandwidth-limited (char A holds)
        reports = amenability_reports()
        assert set(reports) == {"decode", "prefill", "verify"}
        for rep in reports.values():
            assert rep.characteristics[0].passed    # low op/byte
            assert rep.verdict.value in ("amenable", "conditional")
    finally:
        tel.disable()
        tel.reset()


# ---------------------------------------------------------------------------
# check_bench trace gate
# ---------------------------------------------------------------------------

def _load_check_bench():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_bench", os.path.join(os.path.dirname(__file__), os.pardir,
                                    "scripts", "check_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_gate_pass_and_fail(chaos_run, tmp_path):
    cb = _load_check_bench()
    _, b = chaos_run
    good = tmp_path / "good.json"
    b.telemetry.to_perfetto(str(good))
    assert cb.check_trace(str(good)) == 0
    # empty trace fails
    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": []}')
    assert cb.check_trace(str(bad)) > 0
    # a submitted-but-never-retired rid fails
    data = json.loads(good.read_text())
    data["traceEvents"] = [e for e in data["traceEvents"]
                           if e.get("name") != "RETIRE"]
    lost = tmp_path / "lost.json"
    lost.write_text(json.dumps(data))
    assert cb.check_trace(str(lost)) > 0
    # unparseable fails without raising
    garbled = tmp_path / "garbled.json"
    garbled.write_text("{not json")
    assert cb.check_trace(str(garbled)) == 1

"""Scheduler policy units: kv_utilization() aggregation over synthetic
segment samples, and the opt-in skip-ahead admission policy (bounded
lookahead past a head-of-line request whose pages don't fit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig
from repro.serve.scheduler import Batcher, ContinuousBatcher


# --------------------------------------------------------------------------
# kv_utilization aggregation (pure host math — no model needed)
# --------------------------------------------------------------------------

def _batcher_with_samples(samples):
    b = ContinuousBatcher.__new__(ContinuousBatcher)
    b.kv_samples = samples
    return b


def test_kv_utilization_empty():
    u = _batcher_with_samples([]).kv_utilization()
    assert u == {"mean_util": 0.0, "peak_util": 0.0,
                 "peak_live_slots": 0, "samples": 0}


def test_kv_utilization_mean_peak_and_live_slots():
    # (live tokens, allocated token capacity, live slots) per segment
    u = _batcher_with_samples([(10, 100, 2), (50, 100, 3),
                               (30, 60, 1)]).kv_utilization()
    assert u["mean_util"] == pytest.approx((0.1 + 0.5 + 0.5) / 3)
    assert u["peak_util"] == pytest.approx(0.5)
    assert u["peak_live_slots"] == 3
    assert u["samples"] == 3


def test_kv_utilization_skips_zero_capacity_samples():
    """A segment sampled with nothing allocated (cap 0) must not divide by
    zero or drag the mean; live-slot peaks still count every sample."""
    u = _batcher_with_samples([(0, 0, 0), (40, 80, 4),
                               (0, 0, 0)]).kv_utilization()
    assert u["mean_util"] == pytest.approx(0.5)
    assert u["peak_util"] == pytest.approx(0.5)
    assert u["peak_live_slots"] == 4
    assert u["samples"] == 3


def test_unknown_admission_policy_rejected():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    with pytest.raises(ValueError, match="admission"):
        Batcher(model, {}, ServeConfig(max_len=32, batch=2,
                                       admission="lifo"))


# --------------------------------------------------------------------------
# skip-ahead admission
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


def test_skip_ahead_improves_occupancy_mixed_sizes(setup):
    """Mixed prompt sizes against a small pool: FIFO head-of-line blocks
    on the big request and serves alone; skip-ahead admits the small
    requests queued behind it into the idle slots.  Outputs are identical
    either way (per-slot lengths make tokens schedule-independent)."""
    cfg, model, params = setup
    rng = np.random.default_rng(2)
    big = rng.integers(0, cfg.vocab, size=30).tolist()
    smalls = [rng.integers(0, cfg.vocab, size=4).tolist() for _ in range(3)]
    # small first so the pool is part-full when the big head blocks
    requests = [(0, smalls[0]), (1, big), (2, smalls[1]), (3, smalls[2])]
    base = dict(max_len=64, batch=3, dtype=jnp.float32, sync_every=4,
                page_size=8, total_pages=6)

    def run(admission):
        b = Batcher(model, params,
                    ServeConfig(**base, admission=admission))
        for rid, p in requests:
            b.submit(rid, p)
        res = b.run(max_new=8)
        occ = [s for _, _, s in b.kv_samples]
        return res, b.kv_utilization()["peak_live_slots"], occ

    fifo_res, fifo_peak, fifo_occ = run("fifo")
    skip_res, skip_peak, skip_occ = run("skip-ahead")
    for rid, _ in requests:
        assert skip_res[rid] == fifo_res[rid], rid
    # the big request needs 5 of 6 pages: FIFO can never run two slots
    # while it is at the head, skip-ahead packs the smalls in
    assert fifo_peak < skip_peak
    assert skip_peak == 3
    assert (sum(skip_occ) / len(skip_occ)
            > sum(fifo_occ) / len(fifo_occ))


def test_skip_ahead_lookahead_is_bounded(setup):
    """With lookahead 1 the policy degenerates to FIFO: the admissible
    small request sits outside the scan window while the big head
    blocks."""
    cfg, model, params = setup
    rng = np.random.default_rng(6)
    big = rng.integers(0, cfg.vocab, size=30).tolist()
    small = rng.integers(0, cfg.vocab, size=4).tolist()
    base = dict(max_len=64, batch=2, dtype=jnp.float32, sync_every=4,
                page_size=8, total_pages=6)

    def peak(lookahead):
        b = Batcher(model, params,
                    ServeConfig(**base, admission="skip-ahead",
                                admission_lookahead=lookahead))
        # the first small part-fills the pool, so the big head blocks and
        # the last small is only reachable through the lookahead window
        b.submit(0, small)
        b.submit(1, big)
        b.submit(2, small[:3])
        b.run(max_new=8)
        return b.kv_utilization()["peak_live_slots"]

    assert peak(1) == 1      # window stops at the blocked head
    assert peak(3) == 2      # window reaches past it


# --------------------------------------------------------------------------
# skip-ahead aging (starvation bound)
# --------------------------------------------------------------------------

def _aging_batcher(model, params, max_skips):
    """Pool staged so a big head blocks while smalls fit: 14 pages, an
    occupier slot pinning 8, the big request needing 7 > 6 free."""
    scfg = ServeConfig(max_len=64, batch=5, dtype=jnp.float32,
                      page_size=8, total_pages=14,
                      admission="skip-ahead", admission_max_skips=max_skips)
    b = Batcher(model, params, scfg)
    b.pool.reserve(4, 64)          # occupier: 8 pages off the free list
    rng = np.random.default_rng(4)
    big = rng.integers(0, 100, size=48).tolist()      # 7 pages w/ budget 8
    smalls = [rng.integers(0, 100, size=4).tolist() for _ in range(3)]
    b.submit(100, big)
    for i, s in enumerate(smalls):
        b.submit(200 + i, s)
    return b


def test_skip_ahead_aging_becomes_barrier(setup):
    """Each bypass charges the blocked head one skip; at max_skips it
    turns into a barrier — later smalls stop being admitted past it even
    though their pages fit."""
    cfg, model, params = setup
    b = _aging_batcher(model, params, max_skips=2)
    assert b._admit_next(0, 8)[0] == 200          # skip 1 charged to big
    assert b._admit_next(1, 8)[0] == 201          # skip 2 charged to big
    assert b._skips[100] == 2
    # a third small fits (2 of 2 free pages) but the aged head blocks it
    assert b._admit_next(2, 8) is None
    assert b.queue[0][0] == 100 and len(b.queue) == 2
    # pages freeing unblocks the head itself; its skip record clears
    b.pool.release(4)
    assert b._admit_next(2, 8)[0] == 100
    assert 100 not in b._skips
    assert b._admit_next(3, 8)[0] == 202          # queue drains in order
    assert b.admit_order == [200, 201, 100, 202]


def test_skip_ahead_max_skips_zero_is_fifo(setup):
    """max_skips=0 ages the head instantly: skip-ahead degenerates to
    strict FIFO (nothing is ever admitted past a blocked head)."""
    cfg, model, params = setup
    b = _aging_batcher(model, params, max_skips=0)
    assert b._admit_next(0, 8) is None
    assert len(b.queue) == 4 and not b._skips


def test_skip_ahead_aging_full_drain_parity(setup):
    """End to end: aging changes only the admission schedule, never the
    tokens (per-slot lengths keep requests schedule-independent)."""
    cfg, model, params = setup
    rng = np.random.default_rng(8)
    big = rng.integers(0, cfg.vocab, size=30).tolist()
    smalls = [rng.integers(0, cfg.vocab, size=4).tolist() for _ in range(3)]
    requests = [(0, smalls[0]), (1, big), (2, smalls[1]), (3, smalls[2])]
    base = dict(max_len=64, batch=3, dtype=jnp.float32, sync_every=4,
                page_size=8, total_pages=6,
                admission="skip-ahead")

    def run(max_skips):
        b = Batcher(model, params,
                    ServeConfig(**base, admission_max_skips=max_skips))
        for rid, p in requests:
            b.submit(rid, p)
        return b.run(max_new=8), b

    loose, _ = run(max_skips=8)
    tight, bt = run(max_skips=1)
    for rid, _ in requests:
        assert loose[rid] == tight[rid], rid
    # once the big head ages out, the tight run stops packing smalls in
    assert max(bt._skips.values(), default=0) == 0   # drained clean

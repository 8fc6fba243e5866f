"""Self-speculative decoding must be invisible in the tokens: drafting k
candidates from the slot's own history and verifying them in one
multi-token paged attention call (variable per-slot advance, K/V rollback
by not advancing ``lengths``) produces bit-exact greedy output vs the
speculate-off paged engine across every boundary case — mixed prompt
lengths, EOS landing *inside* an accepted speculation window, refills,
prefix-cache hits, chunked-prefill interleave — while ``spec_stats()``
proves drafts were actually accepted where the workload repeats.

Also covers the drafter itself (period extrapolation, repeat-last
fallback), the decode-priority ``prefill_round_tokens`` budget, config
validation, and a hypothesis traffic test driving chunked prefill +
prefix cache + speculation together against the allocator/radix
invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig, ngram_propose
from repro.serve.scheduler import Batcher


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    return cfg, model, params


BASE = dict(max_len=96, batch=3, dtype=jnp.float32, sync_every=4,
            page_size=8, total_pages=36)


def _run(model, params, requests, max_new=10, eos_id=None, **kw):
    b = Batcher(model, params, ServeConfig(**{**BASE, **kw}), eos_id=eos_id)
    for rid, p in requests:
        b.submit(rid, p)
    return b.run(max_new=max_new), b


def _mixed_requests(cfg, sizes, seed=1, system=0):
    rng = np.random.default_rng(seed)
    sys_toks = rng.integers(0, cfg.vocab, size=system).tolist()
    return [(i, sys_toks + rng.integers(0, cfg.vocab, size=n).tolist())
            for i, n in enumerate(sizes)]


def _rep_requests(cfg, n, plen=10, seed=2):
    rng = np.random.default_rng(seed)
    tok = int(rng.integers(0, cfg.vocab))
    return [(i, [tok] * plen) for i in range(n)]


def _assert_parity(ref, got, requests):
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])


def _assert_drained(b):
    assert b.pool.used_pages == 0
    assert int(b.pool.refcount.sum()) == 0
    b.pool.check()


# ---------------------------------------------------------------------------
# the drafter (pure function)
# ---------------------------------------------------------------------------

def test_ngram_period_extrapolation():
    """A period-3 history continues the period, self-referencing drafts
    once the copy source passes the known region."""
    h = jnp.asarray([[1, 2, 3, 1, 2, 3, 1, 2, 0, 0, 0, 0]], jnp.int32)
    d = ngram_propose(h, jnp.asarray([7]), k=5, n=2)
    assert d.tolist() == [[3, 1, 2, 3, 1]]


def test_ngram_single_token_run():
    h = jnp.asarray([[9, 4, 4, 4, 0, 0]], jnp.int32)
    d = ngram_propose(h, jnp.asarray([3]), k=3, n=2)
    assert d.tolist() == [[4, 4, 4]]


def test_ngram_no_match_repeats_current():
    h = jnp.asarray([[5, 6, 7, 0, 0, 0]], jnp.int32)
    d = ngram_propose(h, jnp.asarray([2]), k=3, n=2)
    assert d.tolist() == [[7, 7, 7]]


def test_ngram_per_slot_independent():
    """Rows draft independently: one cycling, one unmatched."""
    h = jnp.asarray([[8, 8, 8, 8, 0, 0],
                     [1, 2, 3, 4, 0, 0]], jnp.int32)
    d = ngram_propose(h, jnp.asarray([3, 3]), k=2, n=2)
    assert d.tolist() == [[8, 8], [4, 4]]


# ---------------------------------------------------------------------------
# bit-exact greedy parity, speculate-on == speculate-off
# ---------------------------------------------------------------------------

def test_spec_parity_mixed_lengths(setup):
    """Chaotic mixed-length prompts: low acceptance, identical tokens."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [9, 3, 14])
    ref, _ = _run(model, params, requests)
    got, b = _run(model, params, requests, speculate_k=3)
    _assert_parity(ref, got, requests)
    assert b.spec_stats()["steps"] > 0
    _assert_drained(b)


def test_spec_parity_repetitive_accepts(setup):
    """The repetitive workload actually exercises acceptance: > 0 drafts
    accepted and > 1 token committed per verify step on average."""
    cfg, model, params = setup
    requests = _rep_requests(cfg, 3)
    ref, _ = _run(model, params, requests, max_new=16)
    got, b = _run(model, params, requests, max_new=16, speculate_k=3)
    _assert_parity(ref, got, requests)
    s = b.spec_stats()
    assert s["accepted"] > 0
    assert s["tokens_per_step"] > 1.0
    _assert_drained(b)


def test_spec_eos_inside_window(setup):
    """EOS committed mid-window: the accepted advance truncates at the
    EOS token (kept, like the plain loop) and the slot retires with its
    pages reclaimed while batch-mates continue."""
    cfg, model, params = setup
    requests = _rep_requests(cfg, 3, seed=5)
    free, _ = _run(model, params, requests, max_new=16)
    # the cycle token appears mid-stream, so with speculation on it is
    # committed from inside an accepted window, not at position 0
    eos = free[0][3]
    ref, _ = _run(model, params, requests, max_new=16, eos_id=eos)
    assert any(len(v) < 16 for v in ref.values())
    got, b = _run(model, params, requests, max_new=16, eos_id=eos,
                  speculate_k=4)
    _assert_parity(ref, got, requests)
    for rid, out in got.items():
        if len(out) < 16:
            assert out[-1] == eos          # EOS kept, nothing after it
    _assert_drained(b)


def test_spec_parity_with_refills(setup):
    """More requests than slots: retirements trigger refills; the fresh
    slot's history row is rebuilt from the new prompt and the old
    request's stale tokens can never influence committed output."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [7, 3, 11, 5, 9, 4], seed=7)
    ref, _ = _run(model, params, requests, max_new=8)
    got, b = _run(model, params, requests, max_new=8, speculate_k=3)
    _assert_parity(ref, got, requests)
    _assert_drained(b)


def test_spec_parity_with_prefix_cache(setup):
    """Speculation over radix-cache hits: shared prefix pages sit below
    every verify write (the k-row overhang lands in private pages), so
    cache-on + spec-on matches cache-off + spec-off bit-for-bit."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [2, 5, 3, 4], seed=9, system=16)
    ref, _ = _run(model, params, requests, max_new=8)
    got, b = _run(model, params, requests, max_new=8, speculate_k=3,
                  prefix_cache=True)
    _assert_parity(ref, got, requests)
    s = b.prefix_stats()
    assert s["hits"] >= 3 and s["prefill_skipped"] > 0
    b.prefix.check()
    assert b.pool.used_pages == 0


def test_spec_parity_with_chunked_prefill(setup):
    """A long prompt chunk-prefills while other slots decode
    speculatively; the frozen slot's placeholder verify writes land in
    its private pages and are overwritten by its next chunk."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [40, 5, 23], seed=11)
    ref, _ = _run(model, params, requests)
    got, b = _run(model, params, requests, speculate_k=3,
                  prefill_chunk=16)
    _assert_parity(ref, got, requests)
    assert b.chunk_joins > 0
    _assert_drained(b)


def test_spec_kernel_route_matches_xla(setup):
    """The verify through the Pallas flash-prefill kernel (interpret on
    CPU) commits the same tokens as the XLA gather route."""
    cfg, model, params = setup
    requests = _rep_requests(cfg, 2, seed=13)
    got_x, _ = _run(model, params, requests, max_new=6, batch=2,
                    speculate_k=3, attn_mode="xla")
    got_k, _ = _run(model, params, requests, max_new=6, batch=2,
                    speculate_k=3, attn_mode="kernel")
    _assert_parity(got_x, got_k, requests)


# ---------------------------------------------------------------------------
# decode-priority chunk budget
# ---------------------------------------------------------------------------

def test_prefill_round_budget_defers_and_preserves_tokens(setup):
    """A tight per-round token budget defers continuation chunks (several
    PREFILLING slots cannot all take a chunk in one round) without
    changing any request's tokens."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [40, 33, 5], seed=15)
    ref, b0 = _run(model, params, requests, prefill_chunk=8)
    got, b1 = _run(model, params, requests, prefill_chunk=8,
                   prefill_round_tokens=8)
    _assert_parity(ref, got, requests)
    assert b1.join_stats()["budget_deferrals"] > 0
    assert b0.join_stats()["budget_deferrals"] == 0
    _assert_drained(b1)


def test_prefill_round_budget_always_progresses(setup):
    """A budget smaller than one chunk still admits one piece per round
    (no livelock) — the cap bounds the round, not the first piece."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [24, 17], seed=17)
    ref, _ = _run(model, params, requests, prefill_chunk=16)
    got, b = _run(model, params, requests, prefill_chunk=16,
                  prefill_round_tokens=1)
    _assert_parity(ref, got, requests)
    _assert_drained(b)


def test_reset_stats_isolates_measurement_waves(setup):
    """A warm batcher re-measured after reset_stats() reports only the
    second wave: acceptance counters and latency inputs start from zero
    (steady-state benchmarking re-submits into the same instance to
    reuse its compiled executables)."""
    cfg, model, params = setup
    requests = _rep_requests(cfg, 3, seed=19)
    b = Batcher(model, params,
                ServeConfig(**{**BASE, "speculate_k": 3}))
    for rid, p in requests:
        b.submit(rid, p)
    b.run(max_new=8)
    first = b.spec_stats()
    assert first["steps"] > 0 and len(b.ttfts) == 3
    b.reset_stats()
    assert b.spec_stats()["steps"] == 0
    assert b.ttfts == [] and b.tpots == [] and not b._first_tok_t
    for rid, p in requests:
        b.submit(rid + 100, p)
    b.run(max_new=8)
    second = b.spec_stats()
    assert second["steps"] == first["steps"]          # one wave, not two
    assert {r - 100: v for r, v in b.results.items() if r >= 100} \
        == {r: v for r, v in b.results.items() if r < 100}
    assert len(b.ttfts) == 3


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_spec_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="speculate_k"):
        Batcher(model, params, ServeConfig(max_len=32, batch=2,
                                           speculate_k=0))
    with pytest.raises(ValueError, match="greedy"):
        Batcher(model, params, ServeConfig(max_len=32, batch=2,
                                           speculate_k=3, temperature=0.7))
    with pytest.raises(ValueError, match="speculate_ngram"):
        Batcher(model, params, ServeConfig(max_len=32, batch=2,
                                           speculate_k=3,
                                           speculate_ngram=0))
    with pytest.raises(ValueError, match="prefill_round_tokens"):
        Batcher(model, params, ServeConfig(max_len=32, batch=2,
                                           prefill_round_tokens=0))


def test_spec_rejects_hybrid_ssm():
    """Recurrent state advances k+1 tokens per verify and cannot roll
    back — hybrid SSM models are rejected up front (before any cache is
    allocated, so no params are needed)."""
    model = Model(get_config("mamba2-370m").reduced())
    with pytest.raises(ValueError, match="attention-only"):
        Batcher(model, None, ServeConfig(max_len=32, batch=2,
                                         speculate_k=3))


def test_spec_window_counts_toward_max_len(setup):
    """prompt + max_new + k must fit max_len: the verify writes (and the
    page reservation covers) up to lengths + k."""
    cfg, model, params = setup
    b = Batcher(model, params,
                ServeConfig(**{**BASE, "speculate_k": 4}))
    b.submit(0, list(range(1, 84)))        # 83 + 10 + 4 > 96
    with pytest.raises(ValueError, match="speculation window"):
        b.run(max_new=10)


# ---------------------------------------------------------------------------
# everything at once: hypothesis traffic
# ---------------------------------------------------------------------------

def test_spec_chunked_prefix_traffic(setup):
    """Random traffic through chunked prefill + prefix cache +
    speculation together: random prompts with shared prefixes, random
    EOS (often landing mid-window), refills — bit-exact parity vs the
    plain paged engine, allocator and radix invariants intact.
    (importorskip inside the test, like test_kvpool, so the rest of this
    module still runs without hypothesis; ci.sh fails loudly when the
    install is missing.)"""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    cfg, model, params = setup

    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def traffic(data):
        rng_seed = data.draw(st.integers(0, 10 ** 6), label="seed")
        rng = np.random.default_rng(rng_seed)
        system = rng.integers(0, cfg.vocab,
                              size=data.draw(st.sampled_from([0, 8, 16]),
                                             label="system")).tolist()
        sizes = data.draw(st.lists(st.integers(1, 30), min_size=2,
                                   max_size=6), label="sizes")
        requests = [(i, system + rng.integers(0, cfg.vocab,
                                              size=n).tolist())
                    for i, n in enumerate(sizes)]
        max_new = data.draw(st.integers(2, 10), label="max_new")
        ref, _ = _run(model, params, requests, max_new=max_new)
        # an output token that exists mid-stream somewhere (or None)
        eos = None
        if data.draw(st.booleans(), label="use_eos"):
            outs = [v for v in ref.values() if len(v) > 2]
            if outs:
                eos = outs[0][1 + rng_seed % (len(outs[0]) - 1)]
                ref2, _ = _run(model, params, requests, max_new=max_new,
                               eos_id=eos)
            else:
                ref2 = ref
        else:
            ref2 = ref
        got, b = _run(model, params, requests, max_new=max_new, eos_id=eos,
                      speculate_k=data.draw(st.sampled_from([1, 3, 4]),
                                            label="k"),
                      prefill_chunk=8, prefix_cache=True,
                      prefill_round_tokens=data.draw(
                          st.sampled_from([None, 8, 24]), label="budget"))
        _assert_parity(ref2, got, requests)
        b.pool.check()
        b.prefix.check()
        assert b.pool.used_pages == 0
        assert int(b.pool.refcount.sum()) == 0

    traffic()

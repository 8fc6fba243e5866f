"""Shared-prefix radix cache over the paged pool: suffix-only prefill must
be invisible in the tokens.  Cache-on output matches the cache-off paged
engine (and the dense step-by-step reference) token-for-token across
mixed suffix lengths, EOS mid-batch, refills re-hitting the cache, and
eviction under a constrained pool — while the stats prove prefill work
was actually skipped and the allocator/radix invariants hold throughout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig
from repro.serve.reference import reference_decode
from repro.serve.scheduler import Batcher


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    rng = np.random.default_rng(11)
    system = rng.integers(0, cfg.vocab, size=17).tolist()  # 2 full pages @ 8
    # mixed suffix lengths, including one page-aligned total prompt (24)
    requests = [(i, system + rng.integers(0, cfg.vocab, size=n).tolist())
                for i, n in enumerate([1, 4, 7, 2])]
    return cfg, model, params, requests


def _run(model, params, scfg, requests, max_new, eos_id=None):
    b = Batcher(model, params, scfg, eos_id=eos_id)
    for rid, p in requests:
        b.submit(rid, p)
    return b.run(max_new=max_new), b


def _assert_drained(b):
    """Post-drain pool state: nothing mapped, cached pages are the only
    thing off the free list, and every invariant holds."""
    assert b.pool.used_pages == 0
    assert b.pool.free_pages + b.pool.cached_pages == b.pool.n_pages
    assert int(b.pool.refcount.sum()) == 0
    b.prefix.check()          # includes pool.check()


def test_prefix_parity_and_skipped_prefill(setup):
    """Cache on == cache off, token for token, with a real token hit rate
    (the shared pages mean most prompts prefill only their suffix)."""
    cfg, model, params, requests = setup
    base = dict(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                page_size=8)
    off, _ = _run(model, params, ServeConfig(**base), requests, max_new=12)
    on, b = _run(model, params, ServeConfig(**base, prefix_cache=True),
                 requests, max_new=12)
    for rid, _ in requests:
        assert on[rid] == off[rid], (rid, on[rid], off[rid])
        assert len(on[rid]) == 12
    s = b.prefix_stats()
    assert s["hits"] == 3                 # all but the first admission
    assert s["prefill_skipped"] == 3 * 16  # two shared pages per hit
    assert s["hit_rate"] > 0.5
    _assert_drained(b)


def test_prefix_parity_vs_dense_reference(setup):
    """The cached path also matches the schedule-free dense reference —
    sharing composes with the paged engine, not just mirrors it."""
    cfg, model, params, requests = setup
    scfg = ServeConfig(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                       page_size=8, prefix_cache=True)
    ref = reference_decode(model, params, scfg, requests, max_new=10)
    got, b = _run(model, params, scfg, requests, max_new=10)
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
    _assert_drained(b)


def test_prefix_refills_rehit_cache(setup):
    """More requests than slots: refills between segments re-hit the
    radix (the prefix pages survive their first holders' retirement in
    the evictable-cached state) and outputs stay schedule-independent."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(3)
    system = rng.integers(0, cfg.vocab, size=16).tolist()
    requests = [(i, system + rng.integers(
        0, cfg.vocab, size=int(rng.integers(1, 6))).tolist())
        for i in range(7)]
    base = dict(max_len=64, batch=2, dtype=jnp.float32, sync_every=4,
                page_size=8)
    off, _ = _run(model, params, ServeConfig(**base), requests, max_new=8)
    on, b = _run(model, params, ServeConfig(**base, prefix_cache=True),
                 requests, max_new=8)
    for rid, _ in requests:
        assert on[rid] == off[rid], (rid, on[rid], off[rid])
    s = b.prefix_stats()
    assert s["hits"] == 6                 # every admission after the first
    assert s["evicted_pages"] == 0        # pool was never under pressure
    _assert_drained(b)


def test_prefix_eos_mid_batch(setup):
    """EOS retirement mid-batch releases the retiring slot's private pages
    while its shared prefix pages stay resident for the cache — parity
    with the cache-off engine is unchanged."""
    cfg, model, params, requests = setup
    base = dict(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                page_size=8)
    free, _ = _run(model, params, ServeConfig(**base), requests, max_new=12)
    eos = free[requests[0][0]][4]
    off, _ = _run(model, params, ServeConfig(**base), requests, max_new=12,
                  eos_id=eos)
    assert any(len(v) < 12 for v in off.values())
    on, b = _run(model, params, ServeConfig(**base, prefix_cache=True),
                 requests, max_new=12, eos_id=eos)
    for rid, _ in requests:
        assert on[rid] == off[rid], (rid, on[rid], off[rid])
    _assert_drained(b)


def test_prefix_eviction_under_constrained_pool(setup):
    """Two alternating system prompts through a pool too small to cache
    both: admission pressure reclaims cached pages (LRU, leaf-first) and
    the outputs still match the cache-off engine exactly."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(9)
    sys_a = rng.integers(0, cfg.vocab, size=16).tolist()
    sys_b = rng.integers(0, cfg.vocab, size=16).tolist()
    requests = [(i, (sys_a if i % 2 == 0 else sys_b) + rng.integers(
        0, cfg.vocab, size=int(rng.integers(1, 5))).tolist())
        for i in range(6)]
    base = dict(max_len=64, batch=1, dtype=jnp.float32, sync_every=4,
                page_size=8, total_pages=4)
    off, _ = _run(model, params, ServeConfig(**base), requests, max_new=6)
    on, b = _run(model, params, ServeConfig(**base, prefix_cache=True),
                 requests, max_new=6)
    for rid, _ in requests:
        assert on[rid] == off[rid], (rid, on[rid], off[rid])
    assert b.prefix_stats()["evicted_pages"] > 0
    _assert_drained(b)


def test_prefix_same_round_hit(setup):
    """Two identical-prefix prompts admitted in the *same* refill round:
    the second matches pages the first is about to fill in the very same
    join call (per layer the pooled scatter precedes the gather), so the
    hit happens with zero intervening decode steps."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(4)
    system = rng.integers(0, cfg.vocab, size=16).tolist()
    requests = [(i, system + rng.integers(
        0, cfg.vocab, size=int(rng.integers(1, 5))).tolist())
        for i in range(3)]
    base = dict(max_len=64, batch=3, dtype=jnp.float32, sync_every=4,
                page_size=8)
    off, _ = _run(model, params, ServeConfig(**base), requests, max_new=8)
    on, b = _run(model, params, ServeConfig(**base, prefix_cache=True),
                 requests, max_new=8)
    for rid, _ in requests:
        assert on[rid] == off[rid], (rid, on[rid], off[rid])
    # all three joined in one round; 2 and 3 still hit pages written by 1
    assert b.prefix_stats()["hits"] == 2
    _assert_drained(b)


def test_prefix_mla_suffix_prefill():
    """The suffix-only prefill also covers MLA's latent cache: resuming a
    paged prefill at depth 8 reproduces the one-shot prefill's logits."""
    cfg = get_config("deepseek-v3-671b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    b, plen, ps = 2, 12, 4
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(b, plen)), jnp.int32)
    n_pages = b * 4
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, -1)
    caches = model.init_paged_caches(b, n_pages, ps, jnp.float32)
    logits_full, _ = model.prefill_paged(
        params, {"tokens": toks}, caches, table, dtype=jnp.float32)
    # two-phase: prefix pages first, then the suffix at cache_len=8
    caches = model.init_paged_caches(b, n_pages, ps, jnp.float32)
    _, caches = model.prefill_paged(
        params, {"tokens": toks[:, :8]}, caches, table, dtype=jnp.float32)
    logits_sfx, _ = model.prefill_paged(
        params, {"tokens": toks[:, 8:]}, caches, table, dtype=jnp.float32,
        cache_len=jnp.full((b,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_full[:, -1]),
                               np.asarray(logits_sfx[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_prefix_requires_paged_and_rejects_ssm(setup):
    cfg, model, params, _ = setup
    mcfg = get_config("mamba2-370m").reduced()
    mmodel = Model(mcfg)
    mparams = pm.unwrap(mmodel.init(jax.random.key(0)))
    with pytest.raises(ValueError, match="SSM"):
        Batcher(mmodel, mparams,
                ServeConfig(max_len=64, batch=2, page_size=8,
                            prefix_cache=True))

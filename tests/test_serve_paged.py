"""Paged KV-cache serving: the block-pool engine (page tables, pooled
pages, page-count bucketing, free-page admission) must produce
token-for-token identical greedy output to the dense step-by-step
reference — mixed prompt lengths, EOS mid-batch, refills, and a pool
smaller than batch * max_len — while reclaiming every retired slot's
pages.  The parity tests run at two page sizes: 8 (several pages per
request) and 16, the serving default."""
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
    rng = np.random.default_rng(1)
    requests = [(i, rng.integers(0, cfg.vocab, size=n).tolist())
                for i, n in enumerate([3, 5, 8, 11])]
    return cfg, model, params, requests


def _run(model, params, scfg, requests, max_new, eos_id=None):
    b = Batcher(model, params, scfg, eos_id=eos_id)
    for rid, p in requests:
        b.submit(rid, p)
    return b.run(max_new=max_new), b


PAGE_SIZES = pytest.mark.parametrize("page_size", [8, 16],
                                     ids=["ps8", "ps16"])


@PAGE_SIZES
def test_paged_parity_greedy_mixed_lengths(setup, page_size):
    """Paged engine == dense per-token reference, bit-exact token ids,
    and the drained pool is fully free again."""
    cfg, model, params, requests = setup
    scfg = ServeConfig(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                       page_size=page_size)
    ref = reference_decode(model, params, scfg, requests, max_new=12)
    got, b = _run(model, params, scfg, requests, max_new=12)
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
        assert len(got[rid]) == 12
    assert b.pool.free_pages == b.pool.n_pages     # 100% reclamation
    assert int(b.pool.refcount.sum()) == 0
    b.pool.check()


@PAGE_SIZES
def test_paged_parity_across_refills(setup, page_size):
    """More requests than slots: retirements free pages between segments
    and the refills join through the page table — outputs independent of
    the slot schedule (per-slot lengths isolate the rows)."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(7)
    requests = [(i, rng.integers(0, cfg.vocab,
                                 size=int(rng.integers(3, 12))).tolist())
                for i in range(7)]
    scfg = ServeConfig(max_len=64, batch=3, dtype=jnp.float32, sync_every=4,
                       page_size=page_size)
    ref = reference_decode(model, params, scfg, requests, max_new=10)
    got, b = _run(model, params, scfg, requests, max_new=10)
    assert set(got) == {rid for rid, _ in requests}
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
    assert b.pool.free_pages == b.pool.n_pages


def test_paged_pool_smaller_than_dense(setup):
    """A pool with fewer tokens than batch * max_len still drains with
    identical outputs: admission blocks on free pages, retirements
    re-admit: the pool's capacity is decoupled from the slot count."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(3)
    requests = [(i, rng.integers(0, cfg.vocab,
                                 size=int(rng.integers(3, 10))).tolist())
                for i in range(6)]
    base = dict(max_len=64, batch=3, dtype=jnp.float32, sync_every=4)
    ref = reference_decode(model, params, ServeConfig(**base), requests,
                           max_new=8)
    # 6 pages x 8 tokens = 48 token capacity vs 3 slots x 64 = 192
    scfg = ServeConfig(**base, page_size=8, total_pages=6)
    got, b = _run(model, params, scfg, requests, max_new=8)
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
    assert b.pool.free_pages == 6
    util = b.kv_utilization()
    assert util["samples"] > 0 and util["peak_util"] > 0.5


@PAGE_SIZES
def test_paged_eos_mid_batch_frees_pages(setup, page_size):
    """EOS retirement mid-batch (EOS kept, the other slots run to budget)
    returns the slot's pages at the segment boundary and keeps parity
    with the reference."""
    cfg, model, params, requests = setup
    scfg = ServeConfig(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                       page_size=page_size)
    free = reference_decode(model, params, scfg, requests, max_new=12)
    eos = free[requests[0][0]][4]
    ref = reference_decode(model, params, scfg, requests, max_new=12,
                           eos_id=eos)
    got, b = _run(model, params, scfg, requests, max_new=12, eos_id=eos)
    assert any(len(v) < 12 for v in ref.values())
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])
        if ref[rid][-1] == eos or len(ref[rid]) < 12:
            assert got[rid][-1] == eos
    assert b.pool.free_pages == b.pool.n_pages


@PAGE_SIZES
def test_paged_kernel_route_matches_xla(setup, page_size):
    """Routing paged decode attention through the Pallas page-table
    kernel (interpret on CPU) changes no sampled ids vs the XLA gather."""
    cfg, model, params, requests = setup
    base = dict(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                page_size=page_size)
    got_x, _ = _run(model, params, ServeConfig(**base, attn_mode="xla"),
                    requests, max_new=8)
    got_k, _ = _run(model, params, ServeConfig(**base, attn_mode="kernel"),
                    requests, max_new=8)
    for rid, _ in requests:
        assert got_x[rid] == got_k[rid], (rid, got_x[rid], got_k[rid])


def test_paged_matches_dense_engine(setup):
    """The paged engine agrees with the model's dense caches, driven step
    by step by the reference loop (same model, different memory
    layout)."""
    cfg, model, params, requests = setup
    scfg = ServeConfig(max_len=64, batch=4, dtype=jnp.float32, sync_every=4,
                       page_size=16)
    dense = reference_decode(model, params, scfg, requests, max_new=10)
    paged, _ = _run(model, params, scfg, requests, max_new=10)
    for rid, _ in requests:
        assert dense[rid] == paged[rid]


def test_paged_ssm_hybrid_across_refills():
    """Hybrid SSM model (mamba2): the paged join must not clobber
    non-joining slots' recurrent SSM state when a retirement triggers a
    refill while other slots are mid-decode — SSM caches are per-slot
    (not paged), and the join scatters back only the joining rows.  The
    oracle is the reference loop over the model's dense caches, SSM
    state included."""
    cfg = get_config("mamba2-370m").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    rng = np.random.default_rng(2)
    requests = [(i, rng.integers(0, cfg.vocab,
                                 size=int(rng.integers(3, 9))).tolist())
                for i in range(5)]

    def run(scfg, eos=None):
        b = Batcher(model, params, scfg, eos_id=eos)
        for rid, p in requests:
            b.submit(rid, p)
        return b.run(max_new=8)

    scfg = ServeConfig(max_len=64, batch=2, dtype=jnp.float32, sync_every=4,
                       page_size=8)
    free = reference_decode(model, params, scfg, requests, max_new=8)
    eos = free[0][2]                   # retires slot 0 mid-stream
    dense = reference_decode(model, params, scfg, requests, max_new=8,
                             eos_id=eos)
    paged = run(scfg, eos=eos)
    assert any(len(v) < 8 for v in dense.values())       # refill happened
    for rid, _ in requests:
        assert dense[rid] == paged[rid], (rid, dense[rid], paged[rid])


def test_paged_mla_matches_dense():
    """The paged layout also covers MLA's latent cache (pools are
    [n_pages, ps, rank] with no head dim): prefill + one decode step on
    an identity page table match the dense path."""
    cfg = get_config("deepseek-v3-671b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    b, plen, max_len, ps = 2, 5, 32, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(b, plen)), jnp.int32)
    logits_d, caches_d = model.prefill(params, {"tokens": toks}, max_len,
                                       dtype=jnp.float32)
    n_pages = b * (max_len // ps)
    caches_p = model.init_paged_caches(b, n_pages, ps, jnp.float32)
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, -1)
    logits_p, caches_p = model.prefill_paged(
        params, {"tokens": toks}, caches_p, table, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_d[:, -1]),
                               np.asarray(logits_p[:, -1]),
                               rtol=2e-5, atol=2e-5)
    nxt = jnp.argmax(logits_d[:, -1], axis=-1).astype(jnp.int32)[:, None]
    ld, _ = model.decode_step(params, nxt, caches_d,
                              jnp.asarray(plen, jnp.int32),
                              dtype=jnp.float32)
    lp, _ = model.decode_step(params, nxt, caches_p,
                              jnp.full((b,), plen, jnp.int32),
                              dtype=jnp.float32, pages=table)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                               rtol=2e-5, atol=2e-5)


def test_paged_rejects_oversized_request(setup):
    """A request that cannot ever fit the pool fails fast instead of
    deadlocking admission."""
    cfg, model, params, _ = setup
    scfg = ServeConfig(max_len=64, batch=2, dtype=jnp.float32,
                       page_size=8, total_pages=4)   # 32 tokens
    b = Batcher(model, params, scfg)
    b.submit(0, list(range(1, 30)))
    with pytest.raises(ValueError, match="pages"):
        b.run(max_new=8)

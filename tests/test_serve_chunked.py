"""Chunked prefill must be invisible in the tokens: admitting long prompts
in page-aligned chunks (PREFILLING slots frozen between chunks, decode
segments interleaved) produces bit-exact greedy output vs the unchunked
paged engine across every boundary case — chunk edges on page edges,
prompts shorter than one chunk, prefix-cache hits leaving a sub-chunk
suffix, EOS retiring one slot while another is mid-prefill — while the
join-latency stats prove the work was actually split."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig
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


def _assert_parity(ref, got, requests):
    for rid, _ in requests:
        assert got[rid] == ref[rid], (rid, got[rid], ref[rid])


def _assert_drained(b):
    assert b.pool.used_pages == 0
    assert int(b.pool.refcount.sum()) == 0
    b.pool.check()


def test_chunked_parity_long_and_short_mixed(setup):
    """A 40-token prompt chunked 16 tokens at a time among short prompts:
    same tokens as the unchunked engine, more (smaller) joins."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [40, 5, 23, 4])
    ref, b0 = _run(model, params, requests)
    got, b1 = _run(model, params, requests, prefill_chunk=16)
    _assert_parity(ref, got, requests)
    assert b1.chunk_joins > 0
    assert b1.join_stats()["joins"] > b0.join_stats()["joins"]
    _assert_drained(b1)


def test_chunk_boundary_on_page_boundary(setup):
    """Chunk edges landing exactly on page edges (and a prompt that is an
    exact multiple of the chunk, so the last chunk is full-width): the
    final chunk commits with zero remainder."""
    cfg, model, params = setup
    # 32 = 2 chunks of 16 = 4 pages of 8 exactly; 48 = 3 chunks exactly
    requests = _mixed_requests(cfg, [32, 48, 16], seed=3)
    ref, _ = _run(model, params, requests)
    got, b = _run(model, params, requests, prefill_chunk=16)
    _assert_parity(ref, got, requests)
    assert b.chunk_joins > 0
    _assert_drained(b)


def test_prompt_shorter_than_one_chunk(setup):
    """Prompts below the chunk size commit on their first join — chunking
    is a no-op (no continuation rounds, same join count)."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [7, 3, 11, 5], seed=5)
    ref, b0 = _run(model, params, requests)
    got, b1 = _run(model, params, requests, prefill_chunk=16)
    _assert_parity(ref, got, requests)
    assert b1.chunk_joins == 0
    assert b1.join_stats()["joins"] == b0.join_stats()["joins"]


def test_prefix_hit_leaves_subchunk_suffix(setup):
    """A prefix-cache hit can shrink a long prompt's uncached suffix below
    one chunk: the hit rows commit immediately at their resumed depth
    while the cache still reports skipped prefill work."""
    cfg, model, params = setup
    # 24 shared tokens = 3 full pages; suffixes 2..9 tokens < chunk 16
    requests = _mixed_requests(cfg, [2, 9, 5, 3], seed=7, system=24)
    ref, _ = _run(model, params, requests)
    got, b = _run(model, params, requests, prefill_chunk=16,
                  prefix_cache=True)
    _assert_parity(ref, got, requests)
    s = b.prefix_stats()
    assert s["hits"] >= 3 and s["prefill_skipped"] > 0
    b.prefix.check()
    assert b.pool.used_pages == 0


def test_eos_mid_batch_while_other_slot_prefilling(setup):
    """A short request hits EOS and retires while the long prompt is
    still PREFILLING: the retirement frees pages at the segment edge, the
    frozen slot is untouched, and tokens match the unchunked engine."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [56, 4, 5], seed=9)
    free, _ = _run(model, params, requests, max_new=12)
    eos = free[1][2]          # a short request's early token as EOS
    ref, _ = _run(model, params, requests, max_new=12, eos_id=eos)
    assert any(len(v) < 12 for v in ref.values())
    got, b = _run(model, params, requests, max_new=12, eos_id=eos,
                  prefill_chunk=8)
    _assert_parity(ref, got, requests)
    assert b.chunk_joins > 0
    _assert_drained(b)


def test_chunked_kernel_route_matches_xla(setup):
    """Chunked suffix prefill through the Pallas flash-prefill kernel
    (interpret on CPU) changes no sampled ids vs the XLA gather path —
    the engine-level pin on the kernel's causal-at-depth math."""
    cfg, model, params = setup
    requests = _mixed_requests(cfg, [21, 4], seed=13)
    got_x, _ = _run(model, params, requests, max_new=4, batch=2,
                    prefill_chunk=8, attn_mode="xla")
    got_k, _ = _run(model, params, requests, max_new=4, batch=2,
                    prefill_chunk=8, attn_mode="kernel")
    _assert_parity(got_x, got_k, requests)


def test_prefill_chunk_validation(setup):
    """Misconfigured chunking is rejected up front: non-positive sizes
    and chunks that straddle page boundaries."""
    cfg, model, params = setup
    for bad in (0, -8, 12):     # 12 % page_size(8) != 0
        with pytest.raises(ValueError, match="prefill_chunk"):
            Batcher(model, params,
                    ServeConfig(max_len=32, batch=2,
                                page_size=8, prefill_chunk=bad))

"""Paged attention kernels vs oracle: permuted page tables, partial last
pages, sentinel (unallocated) tail entries, GQA/MQA head layouts — for the
one-token decode kernel and the multi-token flash-prefill kernel (mixed
per-slot prefix depths, suffixes crossing page boundaries).  Pools are
stacked over layers and stored as the kernels read them (``pack_pages``);
the ops read layer ``LAYER`` of ``LAYERS``, each layer filled with its own
values, so a read of the wrong layer shows."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn import (gather_pages, pack_pages, paged_attn,
                                      paged_attn_ref, paged_attn_xla,
                                      paged_prefill_attn,
                                      paged_prefill_attn_pallas,
                                      paged_prefill_attn_ref)

LAYERS, LAYER = 2, 1


def _pool(rng, n, hkv, ps, d, dtype, layers=LAYERS):
    """A stacked pool [layers, n, hkv, ps / f, f * d] of random pages."""
    return pack_pages(jnp.asarray(
        rng.standard_normal((layers, n, hkv, ps, d)), dtype))


def _mk(rng, b, hq, hkv, d, n, ps, p_max, lengths, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    k = _pool(rng, n, hkv, ps, d, dtype)
    v = _pool(rng, n, hkv, ps, d, dtype)
    # each slot maps ceil(len/ps) random distinct pages; the tail of each
    # row is the pool's sentinel id (== n)
    tbl = np.full((b, p_max), n, np.int32)
    perm = list(rng.permutation(n))
    for i, ln in enumerate(lengths):
        need = -(-ln // ps)
        assert need <= p_max and len(perm) >= need, "test sizing bug"
        for j in range(need):
            tbl[i, j] = perm.pop()
    return q, k, v, jnp.asarray(tbl), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("b,hq,hkv,d", [
    (2, 8, 2, 32),    # GQA 4:1
    (1, 4, 4, 64),    # MHA
    (2, 8, 1, 64),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attn_sweep(b, hq, hkv, d, dtype):
    rng = np.random.default_rng(hq * d)
    n, ps, p_max = 24, 8, 8
    lengths = [int(rng.integers(1, p_max * ps)) for _ in range(b)]
    q, k, v, tbl, ln = _mk(rng, b, hq, hkv, d, n, ps, p_max, lengths, dtype)
    out = paged_attn(q, k, v, tbl, ln, LAYER)
    ref = paged_attn_ref(q, k, v, tbl, ln, LAYER)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("ln", [1, 7, 8, 9, 63, 64])
def test_paged_attn_page_boundaries(ln):
    """Length masking at page boundaries (partial last page, exact fill,
    one-token slot)."""
    rng = np.random.default_rng(ln)
    q, k, v, tbl, lns = _mk(rng, 1, 4, 2, 32, 16, 8, 8, [ln])
    out = paged_attn(q, k, v, tbl, lns, LAYER)
    ref = paged_attn_ref(q, k, v, tbl, lns, LAYER)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("ppb", [1, 2, 4, None])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attn_block_walk(ppb, d, dtype):
    """The block walk at every block size: slots at lengths on the block
    edges (empty, one token, a block less one, a block, a block and one,
    the whole table) under a table of 7 pages, which no block size above
    one divides, over permuted pages with sentinel tails; outputs match
    the oracle, an empty slot reads zeros, and both grid orders give
    bit-identical outputs."""
    from repro.kernels.paged_attn.kernel import default_pages_per_block
    rng = np.random.default_rng(d + (ppb or 0))
    b, hq, hkv, n, ps, p_max = 6, 8, 2, 48, 8, 7
    edge = ps * (ppb or default_pages_per_block(ps, p_max))
    lengths = [min(ln, p_max * ps)
               for ln in (0, 1, edge - 1, edge, edge + 1, p_max * ps)]
    q, k, v, tbl, ln = _mk(rng, b, hq, hkv, d, n, ps, p_max, lengths, dtype)
    outs = [np.asarray(paged_attn(q, k, v, tbl, ln, LAYER, grid_order=order,
                                  pages_per_block=ppb), np.float32)
            for order in ("bh", "hb")]
    np.testing.assert_array_equal(outs[0], outs[1])
    ref = np.asarray(paged_attn_ref(q, k, v, tbl, ln, LAYER), np.float32)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(outs[0][1:], ref[1:], rtol=tol, atol=tol)
    np.testing.assert_array_equal(outs[0][0], 0.0)


def test_paged_attn_matches_dense_decode_attn():
    """A paged cache whose table is the identity permutation is exactly a
    dense cache: paged_attn == decode_attn == dense oracle."""
    from repro.kernels.decode_attn import decode_attn
    rng = np.random.default_rng(0)
    b, hq, hkv, d, ps, p_max = 3, 8, 2, 32, 8, 6
    n = b * p_max
    lengths = [5, 33, 48]
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((b, p_max * ps, hkv, d)),
                     jnp.float32)
    vd = jnp.asarray(rng.standard_normal((b, p_max * ps, hkv, d)),
                     jnp.float32)
    # identity layout: slot i's pages are i*p_max .. i*p_max+p_max-1
    kp = pack_pages(kd.reshape(1, n, ps, hkv, d).swapaxes(2, 3))
    vp = pack_pages(vd.reshape(1, n, ps, hkv, d).swapaxes(2, 3))
    tbl = jnp.arange(n, dtype=jnp.int32).reshape(b, p_max)
    ln = jnp.asarray(lengths, jnp.int32)
    paged = paged_attn(q, kp, vp, tbl, ln)
    dense = decode_attn(q, kd, vd, ln, bs=ps)
    np.testing.assert_allclose(paged, dense, rtol=3e-4, atol=3e-4)


def test_gather_pages_layout():
    """gather_pages reads one layer of a stacked pool, reassembles table
    order, clamps sentinels, and turns head-major pages [L, N, Hkv, ps,
    D] into a token-major view; a packed pool (D 64: two tokens to a
    stored row) gathers the same tokens as its token-row pages."""
    pool = jnp.arange(2 * 4 * 2 * 2, dtype=jnp.float32).reshape(2, 4, 2, 2,
                                                                1)
    tbl = jnp.asarray([[2, 0, 4]], jnp.int32)      # 4 == sentinel, clamps
    out = gather_pages(pool, tbl, 1, 1)
    assert out.shape == (1, 6, 2, 1)
    got = np.asarray(out)[0, :, :, 0] - 16         # [token, head], layer 1
    np.testing.assert_array_equal(got[:4], [[8, 10], [9, 11], [0, 2], [1, 3]])
    rows = jnp.arange(2 * 4 * 2 * 4 * 64,
                      dtype=jnp.float32).reshape(2, 4, 2, 4, 64)
    packed = pack_pages(rows)
    assert packed.shape == (2, 4, 2, 2, 128)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(packed, tbl, 1, 64)),
        np.asarray(rows)[1][[2, 0, 3]].swapaxes(1, 2).reshape(1, 12, 2, 64))
    # latent pages (MLA) have no head axis: [L, N, ps, R]
    lat = jnp.arange(2 * 4 * 2 * 3, dtype=jnp.float32).reshape(2, 4, 2, 3)
    out = gather_pages(lat, tbl, 1)
    assert out.shape == (1, 6, 3)
    np.testing.assert_array_equal(
        np.asarray(out)[0, :4],
        np.asarray(lat)[1][[2, 2, 0, 0], [0, 1, 0, 1]])


def test_paged_attn_xla_matches_kernel():
    rng = np.random.default_rng(9)
    q, k, v, tbl, ln = _mk(rng, 2, 4, 2, 32, 12, 8, 4, [9, 25])
    out_k = paged_attn(q, k, v, tbl, ln, LAYER)
    out_x = paged_attn_xla(q, k, v, tbl, ln, LAYER)
    np.testing.assert_allclose(out_k, out_x, rtol=3e-4, atol=3e-4)


# --------------------------------------------------------------------------
# flash-prefill kernel (multi-token suffix queries at per-slot depths)
# --------------------------------------------------------------------------

def _mk_prefill(rng, b, hq, hkv, d, n, ps, p_max, offsets, lq,
                dtype=jnp.float32):
    """Random pooled pages + per-slot tables sized for offset + lq tokens;
    table tails hold the sentinel id (== n)."""
    q = jnp.asarray(rng.standard_normal((b, lq, hq, d)), dtype)
    k = _pool(rng, n, hkv, ps, d, dtype)
    v = _pool(rng, n, hkv, ps, d, dtype)
    tbl = np.full((b, p_max), n, np.int32)
    perm = list(rng.permutation(n))
    for i, off in enumerate(offsets):
        need = -(-(off + lq) // ps)
        assert need <= p_max and len(perm) >= need, "test sizing bug"
        for j in range(need):
            tbl[i, j] = perm.pop()
    off = jnp.asarray(offsets, jnp.int32)
    return q, k, v, jnp.asarray(tbl), off, off + lq


@pytest.mark.parametrize("b,hq,hkv,d", [
    (2, 8, 2, 32),    # GQA 4:1
    (1, 4, 4, 64),    # MHA
    (2, 8, 1, 64),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_sweep(b, hq, hkv, d, dtype):
    """Kernel vs oracle across head ratios and dtypes at mixed per-slot
    prefix depths (one row deep, one shallow)."""
    rng = np.random.default_rng(hq * d + 1)
    n, ps, p_max, lq = 32, 8, 8, 5
    offsets = [int(rng.integers(0, 3 * ps)) for _ in range(b)]
    q, k, v, tbl, off, ln = _mk_prefill(rng, b, hq, hkv, d, n, ps, p_max,
                                        offsets, lq, dtype)
    out = paged_prefill_attn_pallas(q, k, v, tbl, off, ln, LAYER)
    ref = paged_prefill_attn_ref(q, k, v, tbl, off, ln, LAYER)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("off,lq", [
    (0, 1),      # fresh one-token prompt
    (0, 8),      # exactly one page, no prefix
    (7, 2),      # suffix straddles the first page boundary
    (8, 8),      # page-aligned prefix, page-aligned suffix
    (8, 9),      # page-aligned prefix, suffix crosses into a third page
    (13, 11),    # nothing aligned anywhere
])
def test_paged_prefill_page_boundaries(off, lq):
    """Causal masking at absolute depth across page boundaries: partial
    prefix pages, suffixes crossing pages, exact fills."""
    rng = np.random.default_rng(off * 16 + lq)
    q, k, v, tbl, offs, ln = _mk_prefill(rng, 1, 4, 2, 32, 16, 8, 8,
                                         [off], lq)
    out = paged_prefill_attn_pallas(q, k, v, tbl, offs, ln, LAYER)
    ref = paged_prefill_attn_ref(q, k, v, tbl, offs, ln, LAYER)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_paged_prefill_matches_decode_rowwise():
    """An Lq=1 prefill at depth ``off`` is exactly a decode step whose
    cache already holds off+1 tokens: both kernels agree."""
    rng = np.random.default_rng(3)
    b, hq, hkv, d, ps = 2, 8, 2, 32, 8
    offsets = [5, 19]
    q, k, v, tbl, off, ln = _mk_prefill(rng, b, hq, hkv, d, 24, ps, 8,
                                        offsets, 1)
    pre = paged_prefill_attn_pallas(q, k, v, tbl, off, ln, LAYER)
    dec = paged_attn(q[:, 0], k, v, tbl, ln, LAYER)
    np.testing.assert_allclose(pre[:, 0], dec, rtol=3e-4, atol=3e-4)


def test_paged_prefill_policy_routing():
    """``paged_prefill_attn`` follows the decode-attention policy: the
    kernel path (interpreted here) and the XLA ref agree; ``mode="xla"``
    is the ref bit-for-bit."""
    from repro.kernels.decode_attn import decode_attn_policy
    rng = np.random.default_rng(7)
    q, k, v, tbl, off, ln = _mk_prefill(rng, 2, 8, 2, 32, 24, 8, 8,
                                        [6, 16], 4)
    ref = paged_prefill_attn_ref(q, k, v, tbl, off, ln, LAYER)
    with decode_attn_policy(mode="kernel", interpret=True):
        out_k = paged_prefill_attn(q, k, v, tbl, off, ln, LAYER)
    with decode_attn_policy(mode="xla"):
        out_x = paged_prefill_attn(q, k, v, tbl, off, ln, LAYER)
    np.testing.assert_allclose(out_k, ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(ref))


def test_paged_prefill_dead_pages_skipped():
    """Pages above the causal window never affect the output: corrupting
    every page past ceil((off+lq)/ps) leaves the result bit-identical
    (the §5.1.2 skip really skips)."""
    rng = np.random.default_rng(11)
    n, ps, off, lq = 16, 8, 9, 3
    q, k, v, tbl, offs, ln = _mk_prefill(rng, 1, 4, 2, 32, n, ps, 8,
                                         [off], lq)
    out = paged_prefill_attn_pallas(q, k, v, tbl, offs, ln, LAYER)
    live = {int(p) for p in np.asarray(tbl)[0, :-(-(off + lq) // ps)]}
    dead = [p for p in range(n) if p not in live]
    k2 = k.at[:, jnp.asarray(dead)].set(jnp.nan)
    v2 = v.at[:, jnp.asarray(dead)].set(jnp.nan)
    out2 = paged_prefill_attn_pallas(q, k2, v2, tbl, offs, ln, LAYER)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_prefill_default_row_blocks_overhang():
    """At qwen2-0.5b geometry (14 query heads, 2 KV heads, head_dim 64,
    page 16), a 200-token block fuses 1400 rows: more than one row block
    fits the VMEM budget, so the default splits them into multiple-of-16
    blocks whose last one overhangs the row axis — and still matches the
    oracle."""
    from repro.kernels.paged_attn.prefill_kernel import (ROW_TILE,
                                                         default_block_rows)
    hq, hkv, d, ps, lq = 14, 2, 64, 16, 200
    lg = lq * hq // hkv
    br = default_block_rows(lg, d=d, ps=ps, q_itemsize=4, kv_itemsize=4)
    assert br < lg and br % ROW_TILE == 0 and lg % br
    rng = np.random.default_rng(5)
    q, k, v, tbl, off, ln = _mk_prefill(rng, 1, hq, hkv, d, 20, ps, 16,
                                        [37], lq)
    out = paged_prefill_attn_pallas(q, k, v, tbl, off, ln, LAYER)
    ref = paged_prefill_attn_ref(q, k, v, tbl, off, ln, LAYER)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("off,lq", [
    (7, 4),      # odd depth: the block starts on a row's second token
    (16, 5),     # partial last page
    (10, 1),     # a query at an even position: its row-mate is causal-dead
    (11, 1),     # a query at an odd position sees its whole row
    (3, 26),     # odd depth, a suffix across two page boundaries
])
@pytest.mark.parametrize("d", [64, 128])
def test_paged_prefill_packed_read(off, lq, d):
    """The prefill kernel reads pages as stored: at D 64 a row holds two
    tokens, scored as an even and an odd tile with their own causal
    positions; at D 128 a row is one token.  Layer 1 of 3 of a stacked
    pool, pages of 16, at odd and even depths, partial last pages and a
    causal boundary between the two tokens of a row; pages past the
    causal window hold NaN and change nothing."""
    rng = np.random.default_rng(off * 64 + lq + d)
    b, hq, hkv, n, ps, p_max = 2, 4, 2, 12, 16, 4
    q = jnp.asarray(rng.standard_normal((b, lq, hq, d)), jnp.float32)
    k = _pool(rng, n, hkv, ps, d, jnp.float32, layers=3)
    v = _pool(rng, n, hkv, ps, d, jnp.float32, layers=3)
    assert k.shape[-1] == 128
    need = -(-(off + lq) // ps)
    tbl = np.full((b, p_max), n, np.int32)
    tbl[:, :need] = rng.permutation(n)[:b * need].reshape(b, need)
    offs = jnp.full((b,), off, jnp.int32)
    dead = np.setdiff1d(np.arange(n), tbl[:, :need])
    tbl = jnp.asarray(tbl)
    ref = paged_prefill_attn_ref(q, k, v, tbl, offs, offs + lq, 1)
    k = k.at[:, dead].set(jnp.nan)
    v = v.at[:, dead].set(jnp.nan)
    out = paged_prefill_attn_pallas(q, k, v, tbl, offs, offs + lq, 1)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)

"""Attention: GQA (train / prefill / decode with KV cache) and MLA.

The softmax attention core is blockwise (nested lax.scan over query and key
blocks with an online softmax) whenever the score matrix would be large —
the flash pattern keeps both compiled-HLO size and activation memory O(1)
in sequence length, which matters for the 32k prefill dry-run cells.

GQA never materializes repeated KV heads: queries are reshaped to
[B, L, kv_heads, group, D] and contracted against the unexpanded KV.

MLA (deepseek-v3) follows arXiv:2412.19437: low-rank compressed KV latent
(c_kv, plus a shared RoPE key), low-rank Q; the decode path uses the
*absorbed* form — queries are projected into latent space so the cache
holds only [L, kv_lora + rope_dim] per token.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import param as pm
from .layers import apply_rope, dense, init_dense, init_rmsnorm, rmsnorm
from ..configs.base import ArchConfig

BLOCK_Q = 512
BLOCK_K = 1024
_DENSE_LIMIT = 4096 * 4096   # score elems (per head) above which we go blockwise


class KVCache(NamedTuple):
    k: jnp.ndarray           # [B, S, Hkv, D] (or latent for MLA)
    v: jnp.ndarray           # [B, S, Hkv, D] (or rope-key for MLA)
    length: jnp.ndarray      # [] int32: tokens filled


class PagedKVCache(NamedTuple):
    """Paged layout: K/V pages live in one pooled allocation shared by all
    slots; ``table`` names each slot's pages in order (entries >= n_pages
    are unallocated — scatters through them drop, reads clamp + mask).
    GQA pages are head-major, so a (page, head) block is a contiguous
    ``[page_size, D]`` tile for the paged kernels; MLA's latent pages have
    no head axis.  Either way the token axis is the second-minor one."""
    k: jnp.ndarray           # [n_pages, Hkv, page_size, D] (MLA: [n, ps, r])
    v: jnp.ndarray           # [n_pages, Hkv, page_size, D] (MLA: rope key)
    table: jnp.ndarray       # [B, P] int32 page ids
    length: jnp.ndarray      # [B] int32: tokens filled per slot


# --------------------------------------------------------------------------
# softmax attention cores
# --------------------------------------------------------------------------

def _dense_attn(q, k, v, *, causal: bool, q_offset, kv_len=None):
    """q: [B,Lq,Hkv,G,D], k/v: [B,Lk,Hkv,D].  ``q_offset``/``kv_len`` may
    be per-slot vectors ([B] int32) for continuous-batching decode, where
    each batch row sits at its own depth into the cache."""
    b, lq, hkv, g, d = q.shape
    lk = k.shape[1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(d)
    scores = scores.astype(jnp.float32)
    kpos = jnp.arange(lk)
    off = jnp.asarray(q_offset)
    vec = off.ndim == 1 or (kv_len is not None and jnp.ndim(kv_len) == 1)
    if vec:
        off_b = off if off.ndim == 1 else jnp.broadcast_to(off, (b,))
        qpos = off_b[:, None, None] + jnp.arange(lq)[:, None]   # [B,Lq,1]
        mask = jnp.ones((b, lq, lk), dtype=bool)
        if causal:
            mask &= kpos[None, None, :] <= qpos
        if kv_len is not None:
            kvl = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
            mask &= kpos[None, None, :] < kvl[:, None, None]
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    else:
        qpos = jnp.arange(lq)[:, None] + off
        mask = jnp.ones((lq, lk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", w, v)


def _blockwise_attn(q, k, v, *, causal: bool, q_offset):
    """Flash-style online-softmax attention, O(block) memory."""
    b, lq, hkv, g, d = q.shape
    lk = k.shape[1]
    dv = v.shape[-1]
    bq, bk = min(BLOCK_Q, lq), min(BLOCK_K, lk)
    nq, nk = -(-lq // bq), -(-lk // bk)
    qpad, kpad = nq * bq - lq, nk * bk - lk
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    qb = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, hkv, dv).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / math.sqrt(d)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk

        def k_step(carry, ki_blk):
            m, l, acc = carry
            ki, kblk, vblk = ki_blk
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            qpos = qi * bq + jnp.arange(bq)[:, None] + q_offset
            kpos = ki * bk + jnp.arange(bk)[None, :]
            mask = kpos < lk
            if causal:
                mask &= kpos <= qpos
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(qblk.dtype), vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, ob = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * bq, hkv, g, dv)
    return out[:, :lq]


def _cache_insert(buf: jnp.ndarray, vals: jnp.ndarray, length) -> jnp.ndarray:
    """Write ``vals`` [B, L, ...] into ``buf`` [B, S, ...] starting at
    ``length`` per row.  Scalar lengths use a dynamic slice (one shared
    offset); vector lengths ([B]) scatter per slot — the continuous-batching
    case where each slot is at its own depth.  Out-of-range rows drop."""
    vals = vals.astype(buf.dtype)
    ln = jnp.asarray(length)
    if ln.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, vals, ln, axis=1)
    b, l = vals.shape[:2]
    bidx = jnp.arange(b)[:, None]
    pos = ln[:, None] + jnp.arange(l)[None, :]
    return buf.at[bidx, pos].set(vals, mode="drop")


def _paged_insert(pool: jnp.ndarray, vals: jnp.ndarray,
                  table: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``vals`` [B, L, Hkv, D] into the head-major page pool
    [N, Hkv, ps, D] (or latent ``vals`` [B, L, R] into [N, ps, R]): row
    b's token at sequence position ``length[b] + t`` lands in page
    ``table[b, (length[b] + t) // ps]`` at offset ``% ps``.  Positions
    whose logical page is unallocated (sentinel id >= N) or beyond the
    table width drop — exactly the dense path's out-of-range semantics,
    and how a join masks non-joining rows out of a shared prefill.

    Because the write address is purely position-indexed, the insert is
    **rollback-safe** for speculative decoding: a verify writes k+1 rows
    at ``length .. length + k``, and if only ``a`` of them commit the
    caller simply advances ``length`` by ``a`` — the stale rows above
    the acceptance point are unreachable (every later read is causally
    masked at the new length) and the next verify's scatter, starting at
    the new length, overwrites them.  The scheduler reserves the k-row
    overhang at admission so these writes never land past the slot's
    pages (a dropped write would make a *accepted* draft read garbage)."""
    vals = vals.astype(pool.dtype)
    n, ps = pool.shape[0], pool.shape[-2]
    b, l = vals.shape[:2]
    p_max = table.shape[1]
    pos = jnp.asarray(length, jnp.int32)[:, None] + jnp.arange(l)[None, :]
    logical = pos // ps                                        # [B, L]
    bidx = jnp.arange(b)[:, None]
    page = jnp.where(logical < p_max,
                     table[bidx, jnp.minimum(logical, p_max - 1)], n)
    flat_vals = vals.reshape((b * l,) + vals.shape[2:])
    page, off = page.reshape(-1), (pos % ps).reshape(-1)
    if pool.ndim == 4:
        return pool.at[page, :, off].set(flat_vals, mode="drop")
    return pool.at[page, off].set(flat_vals, mode="drop")


def _paged_gather(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Pages -> contiguous [B, P * ps, ...] view for the XLA attention
    path (sentinels clamp; callers mask by per-slot length)."""
    from ..kernels.paged_attn import gather_pages
    return gather_pages(pool, table)


def _paged_prefill_route(q, cache: "PagedKVCache", q_offset, kv_len):
    """Route multi-token GQA queries over paged KV through the kernel
    package's prefill path: each row's queries sit at its own depth
    ``q_offset`` (0 for a fresh prompt; the resident-prefix length for a
    suffix-only or chunked prefill, where the gather reads shared prefix
    pages — and earlier chunks — in place instead of recomputing them;
    the *decode-time* ``lengths`` for a speculative draft-k verify,
    whose Lq = k+1 block of current-token + drafts is the same causal
    query-block-at-depth — see ``kernels.paged_attn.paged_verify_attn``).
    The op resolves kernel-vs-XLA by the active DecodeAttnPolicy: the
    Pallas flash-prefill kernel on real TPU backends, the gather ref
    elsewhere."""
    from ..kernels.paged_attn import paged_prefill_attn
    return paged_prefill_attn(q, cache.k, cache.v, cache.table,
                              q_offset, kv_len)


def _paged_kernel_route(q, cache: "PagedKVCache", kv_len, dtype):
    """Route one-token GQA decode through the paged Pallas kernel.  The
    grid is the table width — the engine slices the table to its
    page-count bucket, so dead pages are never launched."""
    from ..kernels.paged_attn import paged_attn
    pol = _decode_policy()
    out = paged_attn(q[:, 0], cache.k.astype(dtype), cache.v.astype(dtype),
                     cache.table, kv_len,
                     interpret=pol.resolve_interpret())
    return out[:, None]


def _decode_kernel_route(q, kc, vc, kv_len, dtype):
    """Route one-token GQA decode attention through the Pallas kernel when
    the active policy asks for it.  q: [B,1,Hq,D] -> [B,1,Hq,D].  The
    caller has already applied the policy's kv_cap slice to kc/vc."""
    from ..kernels.decode_attn import decode_attn
    pol = _decode_policy()
    out = decode_attn(q[:, 0], kc.astype(dtype), vc.astype(dtype), kv_len,
                      bs=pol.block_size, interpret=pol.resolve_interpret())
    return out[:, None]


def _decode_policy():
    from ..kernels.decode_attn import active_policy
    return active_policy()


def attention_core(q, k, v, *, causal: bool, q_offset=0, kv_len=None):
    """q: [B,Lq,Hq,D], k: [B,Lk,Hkv,D], v: [B,Lk,Hkv,Dv] (Dv may differ,
    e.g. MLA latents); returns [B,Lq,Hq,Dv]."""
    b, lq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, lq, hkv, g, d)
    if kv_len is None and lq * k.shape[1] > _DENSE_LIMIT:
        out = _blockwise_attn(qg, k, v, causal=causal, q_offset=q_offset)
    else:
        out = _dense_attn(qg, k, v, causal=causal, q_offset=q_offset,
                          kv_len=kv_len)
    return out.reshape(b, lq, hq, v.shape[-1])


# --------------------------------------------------------------------------
# GQA layer
# --------------------------------------------------------------------------

def init_gqa(key: jax.Array, cfg: ArchConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    bias = cfg.qkv_bias
    return {
        "q": init_dense(ks[0], (d, hq, hd), ("embed", "heads", "head_dim"),
                        bias=bias, bias_axes=("heads", "head_dim")),
        "k": init_dense(ks[1], (d, hkv, hd), ("embed", "kv_heads", "head_dim"),
                        bias=bias, bias_axes=("kv_heads", "head_dim")),
        "v": init_dense(ks[2], (d, hkv, hd), ("embed", "kv_heads", "head_dim"),
                        bias=bias, bias_axes=("kv_heads", "head_dim")),
        "o": init_dense(ks[3], (hq, hd, d), ("heads", "head_dim", "embed"),
                        scale=pm.fanin_scale((hq * hd,))),
    }


def gqa_apply(params: dict, x: jnp.ndarray, cfg: ArchConfig, *,
              positions: jnp.ndarray, causal: bool = True,
              cache: KVCache | None = None,
              kv_override: tuple | None = None):
    """x: [B, L, D].  With ``cache``, appends this call's K/V at
    cache.length and attends over the filled prefix (decode/prefill-chunk).
    ``kv_override`` (k, v) turns this layer into cross-attention."""
    from ..distributed.act_sharding import (constrain, constrain_btd,
                                            context_shard_wanted)
    ctx_shard = context_shard_wanted(cfg.n_heads, x.shape[1])
    if ctx_shard:
        # context parallelism: q path seq-sharded; kv replicated (gathered)
        x = constrain(x, ("batch", "ctx", None))
    q = dense(params["q"], x, "btd,dhq->bthq")
    if kv_override is None:
        k = dense(params["k"], x, "btd,dhq->bthq")
        v = dense(params["v"], x, "btd,dhq->bthq")
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
    new_cache = None
    if isinstance(cache, PagedKVCache) and kv_override is None:
        kp = _paged_insert(cache.k, k, cache.table, cache.length)
        vp = _paged_insert(cache.v, v, cache.table, cache.length)
        kv_len = cache.length + x.shape[1]
        new_cache = PagedKVCache(kp, vp, cache.table, kv_len)
        pol = _decode_policy()
        if x.shape[1] == 1 and not ctx_shard and pol.kernel_wanted():
            out = _paged_kernel_route(q, new_cache, kv_len, x.dtype)
        else:
            out = _paged_prefill_route(q, new_cache, cache.length, kv_len)
    elif cache is not None and kv_override is None:
        kc = _cache_insert(cache.k, k, cache.length)
        vc = _cache_insert(cache.v, v, cache.length)
        kv_len = cache.length + x.shape[1]
        new_cache = KVCache(kc, vc, kv_len)
        pol = _decode_policy()
        if pol.kv_cap is not None and pol.kv_cap < kc.shape[1]:
            # grid pruning: the engine bounds the deepest live slot between
            # scan segments, so dead KV blocks never enter the attention op
            kc, vc = kc[:, :pol.kv_cap], vc[:, :pol.kv_cap]
        if x.shape[1] == 1 and not ctx_shard and pol.kernel_wanted():
            out = _decode_kernel_route(q, kc, vc, kv_len, x.dtype)
        else:
            # causal w.r.t. absolute positions (needed for multi-token
            # prefill; no-op for single-token decode where the query is the
            # last position)
            out = attention_core(q, kc.astype(x.dtype), vc.astype(x.dtype),
                                 causal=True, q_offset=cache.length,
                                 kv_len=kv_len)
    else:
        if ctx_shard:
            q = constrain(q, ("batch", "ctx", None, None))
            k = constrain(k, ("batch", None, None, None))
            v = constrain(v, ("batch", None, None, None))
        out = attention_core(q, k, v, causal=causal)
    y = dense(params["o"], out, "bthq,hqd->btd")
    if ctx_shard:
        y = constrain_btd(y)
    return y, new_cache


# --------------------------------------------------------------------------
# MLA layer (deepseek-v3)
# --------------------------------------------------------------------------

def init_mla(key: jax.Array, cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "dq": init_dense(ks[0], (d, m.q_lora_rank), ("embed", "q_lora")),
        "dq_norm": init_rmsnorm(m.q_lora_rank),
        "uq": init_dense(ks[1], (m.q_lora_rank, h, qk + m.qk_rope_head_dim),
                         ("q_lora", "heads", "head_dim")),
        "dkv": init_dense(ks[2], (d, m.kv_lora_rank), ("embed", "kv_lora")),
        "dkv_norm": init_rmsnorm(m.kv_lora_rank),
        "kr": init_dense(ks[3], (d, m.qk_rope_head_dim),
                         ("embed", "head_dim")),
        "uk": init_dense(ks[4], (m.kv_lora_rank, h, qk),
                         ("kv_lora", "heads", "head_dim")),
        "uv": init_dense(ks[5], (m.kv_lora_rank, h, m.v_head_dim),
                         ("kv_lora", "heads", "head_dim")),
        "o": init_dense(ks[6], (h, m.v_head_dim, d),
                        ("heads", "head_dim", "embed"),
                        scale=pm.fanin_scale((h * m.v_head_dim,))),
    }


def _mla_qkr(params, x, cfg, positions):
    m = cfg.mla
    cq = rmsnorm(params["dq_norm"], dense(params["dq"], x, "btd,dr->btr"),
                 cfg.norm_eps)
    q = dense(params["uq"], cq, "btr,rhq->bthq")
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    c_kv = rmsnorm(params["dkv_norm"], dense(params["dkv"], x, "btd,dr->btr"),
                   cfg.norm_eps)
    k_rope = apply_rope(dense(params["kr"], x, "btd,dq->btq")[:, :, None, :],
                        positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(params: dict, x: jnp.ndarray, cfg: ArchConfig, *,
              positions: jnp.ndarray, causal: bool = True,
              cache: KVCache | None = None):
    """MLA with the absorbed decode path: the cache stores the compressed
    latent (c_kv) and the shared rope key only."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, positions)
    # absorb W_uk into the query: q_lat [B,L,H,kv_lora]
    q_lat = jnp.einsum("bthq,rhq->bthr", q_nope,
                       params["uk"]["w"].astype(x.dtype))
    new_cache = None
    if isinstance(cache, PagedKVCache):
        ckv_p = _paged_insert(cache.k, c_kv, cache.table, cache.length)
        kr_p = _paged_insert(cache.v, k_rope, cache.table, cache.length)
        kv_len = cache.length + x.shape[1]
        new_cache = PagedKVCache(ckv_p, kr_p, cache.table, kv_len)
        # MLA's absorbed decode is already a latent gather; the paged path
        # stays on the XLA gather (no per-head pages to walk in the kernel)
        c_kv_all = _paged_gather(ckv_p, cache.table).astype(x.dtype)
        k_rope_all = _paged_gather(kr_p, cache.table).astype(x.dtype)
        q_offset = cache.length
        causal_here = True
    elif cache is not None:
        ckv_c = _cache_insert(cache.k, c_kv, cache.length)
        kr_c = _cache_insert(cache.v, k_rope, cache.length)
        new_cache = KVCache(ckv_c, kr_c, cache.length + x.shape[1])
        pol = _decode_policy()
        if pol.kv_cap is not None and pol.kv_cap < ckv_c.shape[1]:
            ckv_c, kr_c = ckv_c[:, :pol.kv_cap], kr_c[:, :pol.kv_cap]
        c_kv_all, k_rope_all = ckv_c.astype(x.dtype), kr_c.astype(x.dtype)
        kv_len = cache.length + x.shape[1]
        q_offset = cache.length
        causal_here = True
    else:
        c_kv_all, k_rope_all = c_kv, k_rope
        kv_len = None
        q_offset = 0
        causal_here = causal
    # latent attention: keys are [c_kv ; k_rope], queries [q_lat ; q_rope]
    k_full = jnp.concatenate(
        [c_kv_all, k_rope_all], axis=-1)[:, :, None, :]     # [B,S,1,r+rope]
    q_full = jnp.concatenate([q_lat, q_rope], axis=-1)      # [B,L,H,r+rope]
    scale_fix = math.sqrt(q_full.shape[-1]) / math.sqrt(
        m.qk_nope_head_dim + m.qk_rope_head_dim)
    out_lat = attention_core(q_full * scale_fix, k_full,
                             c_kv_all[:, :, None, :],
                             causal=causal_here, q_offset=q_offset,
                             kv_len=kv_len)                  # [B,L,H,kv_lora]
    out = jnp.einsum("bthr,rhv->bthv", out_lat,
                     params["uv"]["w"].astype(x.dtype))
    y = dense(params["o"], out, "bthv,hvd->btd")
    return y, new_cache


def init_attention(key: jax.Array, cfg: ArchConfig) -> dict:
    from ..configs.base import AttnKind
    if cfg.attn is AttnKind.MLA:
        return init_mla(key, cfg)
    return init_gqa(key, cfg)


def attention_apply(params: dict, x: jnp.ndarray, cfg: ArchConfig, **kw):
    from ..configs.base import AttnKind
    if cfg.attn is AttnKind.MLA:
        return mla_apply(params, x, cfg, **kw)
    return gqa_apply(params, x, cfg, **kw)

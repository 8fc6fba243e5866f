"""Oracle: GQA attention gathered through a page table — one-token decode
and multi-token (suffix) prefill at per-slot depth offsets."""
import math

import jax
import jax.numpy as jnp

from ..decode_attn.ref import decode_attn_ref


def gather_pages(pool: jnp.ndarray, table: jnp.ndarray, layer=0,
                 head_dim: int | None = None) -> jnp.ndarray:
    """pool: a stacked pool as stored, head-major K/V pages [L, N, Hkv,
    ps / f, f * D] (``head_dim`` D names the packing, see
    ``kernel.page_shape``), or latent pages [L, N, ps, R] (MLA, no head
    axis, no packing); table: [B, P] int32 page ids (entries >= N are
    unallocated and clamp to the last page — callers mask by length);
    layer: which layer of the stack to read.  Returns the token-major
    contiguous view [B, P * ps, Hkv, D] (or [B, P * ps, R])."""
    gathered = pool[layer, jnp.minimum(table, pool.shape[1] - 1)]
    if pool.ndim == 5:                          # [B, P, Hkv, ps/f, f*D]
        if head_dim is None:
            raise ValueError("a head-major pool needs its head_dim")
        b, p, hkv = gathered.shape[:3]
        gathered = gathered.reshape(b, p, hkv, -1, head_dim)
        gathered = gathered.swapaxes(2, 3)      # [B, P, ps, Hkv, D]
    b, p, ps = gathered.shape[:3]
    return gathered.reshape((b, p * ps) + gathered.shape[3:])


def paged_attn_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                   v_pages: jnp.ndarray, table: jnp.ndarray,
                   lengths: jnp.ndarray, layer=0) -> jnp.ndarray:
    """q: [B, Hq, D]; k_pages/v_pages: stacked pools as stored [L, N, Hkv,
    ps / f, f * D]; table: [B, P]; lengths: [B] int32 — slot b attends
    over its first lengths[b] tokens of layer ``layer`` in page-table
    order."""
    d = q.shape[-1]
    k = gather_pages(k_pages, table, layer, d)
    v = gather_pages(v_pages, table, layer, d)
    return decode_attn_ref(q, k, v, lengths)


def paged_prefill_attn_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, table: jnp.ndarray,
                           q_offset: jnp.ndarray,
                           kv_len: jnp.ndarray, layer=0) -> jnp.ndarray:
    """Multi-token causal GQA attention through a page table: q [B, L, Hq,
    D] are suffix queries sitting at per-slot depths ``q_offset`` [B] (the
    cached-prefix lengths of a suffix-only prefill); slot b's query at
    position ``q_offset[b] + t`` attends over its first
    ``min(q_offset[b] + t + 1, kv_len[b])`` gathered tokens of layer
    ``layer`` of the stacked pools.

    The math mirrors models.attention._dense_attn's vectorized branch
    exactly (same einsum contractions, f32 score masking, weights cast
    back to the query dtype) so routing a prefill through the pages is
    bit-identical to the dense path the parity tests pin."""
    b, lq, hq, d = q.shape
    k = gather_pages(k_pages, table, layer, d).astype(q.dtype)
    v = gather_pages(v_pages, table, layer, d).astype(q.dtype)
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, lq, hkv, g, d)
    lk = k.shape[1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / math.sqrt(d)
    scores = scores.astype(jnp.float32)
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1),
                           (b,))
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    kpos = jnp.arange(lk)
    qpos = off[:, None, None] + jnp.arange(lq)[:, None]       # [B, Lq, 1]
    mask = (kpos[None, None, :] <= qpos) \
        & (kpos[None, None, :] < kvl[:, None, None])          # [B, Lq, Lk]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(b, lq, hq, v.shape[-1])

"""Decoder-only LM (+ hybrid SSM / MoE / enc-dec variants) with
scan-over-layers.

Layer stacks are built as *segments* of identical blocks whose parameters
are stacked on a leading axis and applied with ``lax.scan`` — compiled HLO
is O(segments), not O(layers), which keeps 61-layer MoE and 48-layer hybrid
models lowerable for 512-device meshes.  Heterogeneous stacks (deepseek's
dense prefix, zamba2's shared attention) are sequences of homogeneous
segments; zamba2's shared block re-applies one weight set at every
occurrence.

The LM loss is computed chunked over the sequence (logits for a chunk are
formed, reduced against targets, and discarded) so the [tokens, vocab]
logits tensor never materializes — at vocab 256k that matters more than
any other activation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import param as pm
from .attention import (KVCache, PagedKVCache, PoolLayer, attention_apply,
                        init_attention)
from .layers import (dense, embed, init_dense, init_embedding, init_layernorm,
                     init_mlp, init_rmsnorm, layernorm, mlp, rmsnorm, unembed)
from .moe import init_moe, moe_apply
from .ssm import SsmCache, init_cache as init_ssm_cache, init_ssm, ssm_apply
from ..configs.base import ArchConfig, AttnKind, BlockKind, Segment

LOSS_CHUNK = 1024


# ---------------------------------------------------------------------------
# norms (rms vs layer, config-driven)
# ---------------------------------------------------------------------------

def _init_norm(cfg: ArchConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    return init_layernorm(d) if cfg.enc_dec else init_rmsnorm(d)


def _norm(cfg: ArchConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.enc_dec:
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ArchConfig, kind: BlockKind, *,
               cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    if kind is BlockKind.SSM:
        return {"norm": _init_norm(cfg), "ssm": init_ssm(ks[0], cfg)}
    out = {"norm1": _init_norm(cfg), "attn": init_attention(ks[0], cfg),
           "norm2": _init_norm(cfg)}
    if cross:
        out["norm_x"] = _init_norm(cfg)
        out["cross"] = init_attention(ks[3], cfg)
    if kind is BlockKind.MOE:
        out["moe"] = init_moe(ks[1], cfg)
    else:
        out["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                              gated=cfg.gated_mlp)
    return out


def block_apply(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                kind: BlockKind, *, positions, cache=None,
                cross_kv=None, causal: bool = True):
    """Returns (y, new_cache, aux_loss)."""
    from ..distributed.act_sharding import constrain_btd
    x = constrain_btd(x)   # §Perf iter 1: pin activations to batch sharding
    aux = jnp.zeros((), jnp.float32)
    if kind is BlockKind.SSM:
        h, new_cache = ssm_apply(params["ssm"],
                                 _norm(cfg, params["norm"], x), cfg,
                                 cache=cache)
        return x + h, new_cache, aux
    h, new_cache = attention_apply(params["attn"],
                                   _norm(cfg, params["norm1"], x), cfg,
                                   positions=positions, causal=causal,
                                   cache=cache)
    x = x + h
    if "cross" in params and cross_kv is not None:
        h, _ = attention_apply(params["cross"],
                               _norm(cfg, params["norm_x"], x), cfg,
                               positions=positions, causal=False,
                               kv_override=cross_kv)
        x = x + h
    z = _norm(cfg, params["norm2"], x)
    if kind is BlockKind.MOE:
        h, aux = moe_apply(params["moe"], z, cfg, cfg.activation)
    else:
        h = mlp(params["mlp"], z, cfg.activation)
    return x + h, new_cache, aux


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _attn_cache_shape(cfg: ArchConfig, batch: int, max_len: int):
    if cfg.attn is AttnKind.MLA:
        return ((batch, max_len, cfg.mla.kv_lora_rank),
                (batch, max_len, cfg.mla.qk_rope_head_dim))
    hd = cfg.resolved_head_dim
    return ((batch, max_len, cfg.kv_heads, hd),
            (batch, max_len, cfg.kv_heads, hd))


def init_caches(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> list:
    """One cache pytree per segment (stacked over the segment's layers)."""
    caches = []
    kshape, vshape = _attn_cache_shape(cfg, batch, max_len)
    for seg in cfg.resolved_segments():
        n = seg.count
        if seg.kind is BlockKind.SSM:
            single = init_ssm_cache(cfg, batch, dtype)
            caches.append(jax.tree_util.tree_map(
                lambda a: jnp.zeros((n,) + a.shape, a.dtype), single))
        else:
            caches.append({
                "k": jnp.zeros((n,) + kshape, dtype),
                "v": jnp.zeros((n,) + vshape, dtype)})
    return caches


def init_paged_caches(cfg: ArchConfig, batch: int, n_pages: int,
                      page_size: int, dtype=jnp.bfloat16) -> list:
    """Paged variant of :func:`init_caches`: attention segments hold one
    pooled ``[layers, n_pages, kv_heads, page_size / f, f * head_dim]``
    allocation, stored as the paged kernels read it (head-major, ``f``
    tokens to a 128-lane row where head_dim divides 128; see PagedKVCache
    and ``kernels.paged_attn.page_shape``; MLA latents ``[layers,
    n_pages, page_size, r]``), shared by every slot through the page
    table (see repro.serve.kvpool); SSM segments keep their per-slot
    recurrent state — it is O(1) in sequence length, there is nothing to
    page (which is also why prefix sharing is attention-only: a recurrent
    state cannot resume from a cached page)."""
    from ..kernels.paged_attn import page_shape
    caches = []
    kshape, vshape = _attn_cache_shape(cfg, n_pages, page_size)
    if cfg.attn is not AttnKind.MLA:
        kshape = vshape = (n_pages, cfg.kv_heads) + page_shape(
            page_size, cfg.resolved_head_dim)
    for seg in cfg.resolved_segments():
        n = seg.count
        if seg.kind is BlockKind.SSM:
            single = init_ssm_cache(cfg, batch, dtype)
            caches.append(jax.tree_util.tree_map(
                lambda a: jnp.zeros((n,) + a.shape, a.dtype), single))
        else:
            caches.append({
                "k": jnp.zeros((n,) + kshape, dtype),
                "v": jnp.zeros((n,) + vshape, dtype)})
    return caches


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_lm(key: jax.Array, cfg: ArchConfig) -> dict:
    ks = iter(jax.random.split(key, 64))
    params: dict[str, Any] = {"embed": init_embedding(next(ks), cfg.vocab,
                                                      cfg.d_model)}
    segments = []
    for seg in cfg.resolved_segments():
        if seg.kind is BlockKind.SHARED_ATTN:
            segments.append({})   # weights live in params["shared_block"]
            continue
        keys = jax.random.split(next(ks), seg.count)
        stacked = jax.vmap(
            lambda k: init_block(k, cfg, seg.kind, cross=cfg.enc_dec)
        )(keys)
        segments.append(stacked)
    params["segments"] = segments
    if cfg.shared_attn_every:
        params["shared_block"] = init_block(next(ks), cfg, BlockKind.DENSE)
    params["final_norm"] = _init_norm(cfg)
    if not cfg.tied_embeddings:
        params["lm_head"] = init_dense(next(ks), (cfg.d_model, cfg.vocab),
                                       ("embed_r", "vocab"))
    if cfg.mtp:
        params["mtp_block"] = init_block(next(ks), cfg, BlockKind.DENSE)
        params["mtp_norm"] = _init_norm(cfg)
    if cfg.enc_dec:
        enc_cfg = dataclasses.replace(cfg, attn=AttnKind.GQA,
                                      kv_heads=cfg.n_heads)
        keys = jax.random.split(next(ks), cfg.n_encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: init_block(k, enc_cfg, BlockKind.DENSE))(keys)
        params["enc_norm"] = _init_norm(cfg)
        params["cross_k"] = init_dense(
            next(ks), (cfg.d_model, cfg.kv_heads, cfg.resolved_head_dim),
            ("embed", "kv_heads", "head_dim"))
        params["cross_v"] = init_dense(
            next(ks), (cfg.d_model, cfg.kv_heads, cfg.resolved_head_dim),
            ("embed", "kv_heads", "head_dim"))
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _scan_segment(stacked, x, cfg, kind, *, positions, offset, cache,
                  cross_kv, causal, remat):
    """cache: None | {"k","v"} dense, stacked | SsmCache of stacked arrays
    (the paged pools have a layer loop of their own,
    :func:`_paged_layer_loop`)."""
    is_ssm = kind is BlockKind.SSM

    def call(p, h, c):
        return block_apply(p, h, cfg, kind, positions=positions, cache=c,
                           cross_kv=cross_kv, causal=causal)

    if remat:
        call = jax.checkpoint(call)

    if cache is None:
        def body(carry, p):
            h, aux = carry
            y, _, a = call(p, h, None)
            return (y, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   stacked)
        return x, None, aux

    def body(carry, xs):
        h, aux = carry
        p, craw = xs
        if is_ssm:
            c = craw
        else:
            c = KVCache(craw["k"], craw["v"], offset)
        y, new_c, a = call(p, h, c)
        if not is_ssm:
            new_c = {"k": new_c.k, "v": new_c.v}
        return (y, aux + a), new_c
    (x, aux), new_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stacked, cache))
    return x, new_cache, aux


def _paged_layer_loop(stacked, x, cfg, kind, *, positions, offset, cache,
                      cross_kv, pages):
    """The layer loop of an attention segment over the paged pools
    ``cache`` ({"k","v"}, stacked ``[L, N, ...]``), addressed through the
    page table ``pages`` ([B, P] int32, shared by every layer).  The
    carry holds the pools next to the activations; the layer's weights
    and its index are the scan inputs.  Each layer's attention scatters
    its new rows into ``pool[layer]`` and its kernel reads ``pool[layer]``
    through that index, so the pools stay one buffer that the loop
    updates in place — no layer is sliced out, relaid out or stacked
    back."""
    def body(carry, xs):
        h, aux, kpool, vpool = carry
        p, layer = xs
        c = PagedKVCache(PoolLayer(kpool, layer), PoolLayer(vpool, layer),
                         pages, offset)
        y, new_c, a = block_apply(p, h, cfg, kind, positions=positions,
                                  cache=c, cross_kv=cross_kv)
        return (y, aux + a, new_c.k.pool, new_c.v.pool), None
    layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    (x, aux, k, v), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), cache["k"], cache["v"]),
        (stacked, layers))
    return x, {"k": k, "v": v}, aux


def encode(params: dict, frames: jnp.ndarray, cfg: ArchConfig):
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    from .layers import sinusoidal_positions
    x = frames + sinusoidal_positions(frames.shape[1],
                                      cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(frames.shape[1])
    x, _, _ = _scan_segment(params["encoder"], x, cfg, BlockKind.DENSE,
                            positions=positions, offset=0, cache=None,
                            cross_kv=None, causal=False, remat=False)
    x = _norm(cfg, params["enc_norm"], x)
    k = dense(params["cross_k"], x, "btd,dhq->bthq")
    v = dense(params["cross_v"], x, "btd,dhq->bthq")
    return (k, v)


def forward(params: dict, batch: dict, cfg: ArchConfig, *,
            caches: list | None = None, cache_len: jnp.ndarray | None = None,
            dtype=jnp.bfloat16, remat: bool = False,
            pages: jnp.ndarray | None = None):
    """Returns (hidden [B,L,D], new_caches, aux_loss).

    batch: tokens [B, L]; optional vision_embeds [B, Tv, D] (prefix),
    encoder_frames [B, Te, D] or cross_kv (precomputed encoder output).
    ``pages`` ([B, P] int32): attention caches are the paged pools from
    :func:`init_paged_caches`, addressed through this per-slot page table
    (``cache_len`` must then be per-slot, [B] int32).

    Multi-token calls at nonzero per-slot ``cache_len`` are the
    suffix-only prefill (serve prefix cache): row b's L tokens sit at
    absolute positions ``cache_len[b] + t`` — positions drive RoPE and
    the causal mask, paged K/V scatters land past the resident prefix,
    and attention gathers the prefix pages through the table instead of
    recomputing them.  The speculative draft-k verify is the same call
    shape at decode time (L = k+1 at ``cache_len`` = the slot's live
    length): nothing in the stack distinguishes a prompt chunk from a
    draft window — the caller decides how far ``cache_len`` advances
    afterwards, which is what makes rollback free.
    """
    from ..distributed.act_sharding import constrain_btd
    tokens = batch["tokens"]
    x = constrain_btd(embed(params["embed"], tokens, dtype))
    if cfg.frontend.value == "vision" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(dtype)
        x = jnp.concatenate([ve, x[:, ve.shape[1]:]], axis=1)
    cross_kv = batch.get("cross_kv")
    if cfg.enc_dec and cross_kv is None and "encoder_frames" in batch:
        cross_kv = encode(params, batch["encoder_frames"].astype(dtype), cfg)

    length = x.shape[1]
    offset = cache_len if cache_len is not None else 0
    # per-slot cache depths (continuous batching): positions become [B, L]
    if getattr(offset, "ndim", 0) == 1:
        positions = offset[:, None] + jnp.arange(length)[None, :]
    else:
        positions = offset + jnp.arange(length)

    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    segs = cfg.resolved_segments()
    for i, seg in enumerate(segs):
        cache_i = caches[i] if caches is not None else None
        if seg.kind is BlockKind.SHARED_ATTN:
            c = None
            if cache_i is not None and pages is not None:
                # the shared block's pools, addressed in place at layer 0
                layer = jnp.zeros((), jnp.int32)
                c = PagedKVCache(PoolLayer(cache_i["k"], layer),
                                 PoolLayer(cache_i["v"], layer), pages,
                                 offset)
            elif cache_i is not None:
                c = KVCache(cache_i["k"][0], cache_i["v"][0], offset)
            y, nc, aux = block_apply(params["shared_block"], x, cfg,
                                     BlockKind.DENSE, positions=positions,
                                     cache=c, cross_kv=cross_kv)
            if isinstance(nc, PagedKVCache):
                nc = {"k": nc.k.pool, "v": nc.v.pool}
            elif cache_i is not None:
                nc = {"k": nc.k[None], "v": nc.v[None]}
            new_caches.append(nc)
        elif (pages is not None and cache_i is not None
              and seg.kind is not BlockKind.SSM):
            y, nc, aux = _paged_layer_loop(
                params["segments"][i], x, cfg, seg.kind,
                positions=positions, offset=offset, cache=cache_i,
                cross_kv=cross_kv, pages=pages)
            new_caches.append(nc)
        else:
            y, nc, aux = _scan_segment(
                params["segments"][i], x, cfg, seg.kind,
                positions=positions, offset=offset, cache=cache_i,
                cross_kv=cross_kv, causal=True, remat=remat)
            new_caches.append(nc)
        x = y
        aux_total = aux_total + aux
    x = _norm(cfg, params["final_norm"], x)
    return x, (new_caches if caches is not None else None), aux_total


def logits_fn(params: dict, hidden: jnp.ndarray, cfg: ArchConfig):
    if cfg.tied_embeddings:
        return unembed(params["embed"], hidden)
    return dense(params["lm_head"], hidden.astype(jnp.float32),
                 "btd,dv->btv")


# ---------------------------------------------------------------------------
# chunked LM loss
# ---------------------------------------------------------------------------

def chunked_xent(params: dict, hidden: jnp.ndarray, targets: jnp.ndarray,
                 cfg: ArchConfig, mask: jnp.ndarray | None = None,
                 chunk: int = LOSS_CHUNK, z_loss: float = 1e-4):
    """Cross-entropy without materializing [B, L, V]."""
    b, l, d = hidden.shape
    nc = -(-l // chunk)
    pad = nc * chunk - l
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None
                       else jnp.ones((b, l), bool), ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((b, l), bool)
    hc = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)
    tc = targets.reshape(b, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

    def body(carry, xs):
        from ..distributed.act_sharding import constrain
        loss_sum, count = carry
        h, t, m = xs
        h = constrain(h, ("batch", None, None))
        logits = logits_fn(params, h, cfg)              # [B, chunk, V] f32
        logits = constrain(logits, ("batch", None, "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) + z_loss * jnp.square(lse)
        loss_sum = loss_sum + jnp.sum(nll * m)
        count = count + jnp.sum(m)
        return (loss_sum, count), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, tc, mc))
    return loss_sum / jnp.maximum(count, 1.0)


def lm_loss(params: dict, batch: dict, cfg: ArchConfig, *,
            dtype=jnp.bfloat16, remat: bool = False):
    """Next-token loss (+ optional deepseek-style MTP auxiliary loss)."""
    tokens = batch["tokens"]
    hidden, _, aux = forward(params, batch, cfg, dtype=dtype, remat=remat)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=bool).at[:, -1].set(False)
    loss = chunked_xent(params, hidden, targets, cfg, mask)
    if cfg.mtp:
        positions = jnp.arange(tokens.shape[1])
        h2, _, _ = block_apply(params["mtp_block"], hidden, cfg,
                               BlockKind.DENSE, positions=positions)
        h2 = _norm(cfg, params["mtp_norm"], h2)
        t2 = jnp.roll(tokens, -2, axis=1)
        m2 = mask.at[:, -2].set(False)
        loss = loss + 0.3 * chunked_xent(params, h2, t2, cfg, m2)
    if cfg.moe is not None:
        loss = loss + 0.01 * aux
    return loss

"""Paged GQA decode attention (flash-decode over a page table).

Same regime as :mod:`repro.kernels.decode_attn` — one new token against a
deep KV cache, memory-bound, accumulator staged in VMEM across the KV walk
— but the cache is no longer a per-slot stripe: K/V pages live in one
pooled head-major ``[n_pages, Hkv, page_size, D]`` allocation and each
slot names its pages through a ``[B, max_pages]`` table.  The indirection happens in the
BlockSpec index maps: the page table and per-slot lengths are
scalar-prefetched, so the DMA for grid step ``(b, h, p)`` fetches physical
page ``table[b, p]`` — the gather costs nothing extra, it just redirects
the block fetch.

Command skipping (§5.1.2) lands at page granularity and at two levels:

* inside the kernel, ``pl.when(page_base < len)`` makes every page past a
  slot's live length a no-op (the accumulator carries through), and a dead
  page's DMA is redirected to the slot's first page so no fresh HBM line
  is even touched;
* the caller prunes the grid itself by slicing the table to a host-known
  bound on the deepest live slot's page count (see ops.paged_attn /
  the engine's page-count bucketing) — pages past *every* slot's length
  are never launched.

Pages are stored head-major so that one grid step's K/V block,
``(1, 1, page_size, D)``, has the page's token rows on sublanes and D on
lanes as its two minor dimensions.  Mosaic requires those two to be
multiples of (8, 128) or the array's full extent; both are the full
extent here, so the block is legal at any page size and head_dim (a
token-major ``[n_pages, page_size, Hkv, D]`` page would put a 1-wide
head slice on sublanes, which the TPU compiler refuses).

Tunable launch geometry (see :mod:`autotune`): ``grid_order`` picks which
of the two outer grid axes is major — ``"bh"`` walks slots outermost
(each slot's heads, then pages, consecutively), ``"hb"`` walks KV heads
outermost (all slots' page walks for one head before the next head —
better pool-page locality when slots share prefix pages).  The page axis
always stays innermost: the flash accumulator scratch is carried across
grid steps and must see a slot-head's full page walk contiguously.
Either order visits the same pages with the same per-(slot, head)
accumulation sequence, so outputs are bit-identical.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GRID_ORDERS = ("bh", "hb")     # batch-major / head-major outer walk


def _axes(grid_order: str) -> tuple[int, int]:
    """(batch_axis, head_axis) grid positions for ``grid_order``."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {GRID_ORDERS}, "
                         f"got {grid_order!r}")
    return (0, 1) if grid_order == "bh" else (1, 0)


def _make_kernel(ps: int, scale: float, b_axis: int):
    def kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref):
        bi = pl.program_id(b_axis)
        p = pl.program_id(2)
        np_ = pl.num_programs(2)
        ln = len_ref[bi]

        @pl.when(p == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        base = p * ps
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)

        # page-granular command skipping: pages past *this slot's* live
        # length do no compute (and their DMA was redirected to page 0 of
        # the slot by the index map, so no new HBM line was pulled either)
        @pl.when(base < ln)
        def _():
            q = q_ref[0, 0]                  # [G, D]
            k = k_ref[0, 0]                  # [ps, D]
            v = v_ref[0, 0]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [G, ps]
            live = kpos < ln                 # [1, ps] (partial last page)
            scores = jnp.where(live, scores, -1e30)
            m_prev = m_ref[...]              # [G, 1]
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + pexp.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                pexp.astype(jnp.float32), v.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(p == np_ - 1)
        def _():
            o_ref[0, 0] = (acc_ref[...]
                           / jnp.maximum(l_ref[...], 1e-30)
                           ).astype(o_ref.dtype)
    return kernel


def paged_attn_kernel(q: jnp.ndarray, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, table: jnp.ndarray,
                      lengths: jnp.ndarray, *,
                      interpret: bool = True,
                      grid_order: str = "bh") -> jnp.ndarray:
    """q: [B, Hkv, G, D]; k_pages/v_pages: [N, Hkv, ps, D] pooled pages;
    table: [B, P] int32 physical page per (slot, logical page) — every
    entry must be < N (callers clamp sentinels); lengths: [B] int32.
    ``grid_order`` picks the outer grid majorness (see module docstring);
    the page axis is always innermost."""
    b, hkv, g, d = q.shape
    ps = k_pages.shape[2]
    p_max = table.shape[1]
    b_axis, h_axis = _axes(grid_order)
    grid = [0, 0, p_max]
    grid[b_axis], grid[h_axis] = b, hkv
    grid = tuple(grid)

    def kv_map(i0, i1, p, tbl, ln):
        bi, h = (i0, i1)[b_axis], (i0, i1)[h_axis]
        # dead pages re-fetch the slot's first page (always resident for a
        # live slot) instead of pulling a fresh line that will be skipped
        pg = jnp.where(p * ps < ln[bi], tbl[bi, p], tbl[bi, 0])
        return (pg, h, 0, 0)

    def q_map(i0, i1, p, tbl, ln):
        bi, h = (i0, i1)[b_axis], (i0, i1)[h_axis]
        return (bi, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel(ps, 1.0 / math.sqrt(d), b_axis), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret)(table, lengths, q, k_pages, v_pages)

"""Paged GQA decode attention (flash-decode over a page table).

Same regime as :mod:`repro.kernels.decode_attn` — one new token against a
deep KV cache, memory-bound, accumulator staged in VMEM across the KV walk
— but the cache is no longer a per-slot stripe: K/V pages live in one
pooled head-major ``[n_pages, Hkv, page_size, D]`` allocation and each
slot names its pages through a ``[B, max_pages]`` table.

Block walk.  The grid is ``(B, Hkv, cdiv(P, pages_per_block))``: each
step attends over a *block* of ``pages_per_block`` consecutive table
entries, ``table[b, j*ppb : (j+1)*ppb]``, as one ``[G, ppb * page_size]``
score tile with one online-softmax update (max, rescale, ``l``) per
block.  A grid step costs a fixed pipeline overhead whatever it computes,
and one 16-token page fills 16 of a vreg's 128 lanes, so walking pages
one per step made the step count, not the bytes, set the kernel's time.

The K/V pools stay in HBM (``memory_space=pl.ANY``).  A live step gathers
its block with one async copy per page, ``pool[table[b, p], h]`` →
``buf[slot, p - j*ppb]``, into a double-buffered ``[2, ppb, rows, lanes]``
VMEM buffer (a page's ``[page_size, D]`` tile, or its lane-packed view:
below), and it starts the copies of the *next live* block —
the next block of this slot-head, or the first block of the next
(slot, head) pair in grid order whose slot holds any token — into the
other half before it computes on its own.  So a live step's DMAs overlap
the previous live step's compute, across slot and head boundaries too.
The first live step of the grid starts its own copies; an SMEM pair
carries the buffer half and whether the current block was prefetched
from one grid step to the next.

Command skipping (§5.1.2) lands at three levels:

* a block at or past ``ceil(len / page_size)`` pages issues no copy and
  no compute (the accumulator carries through); only the grid step
  itself remains;
* inside the last live block, pages past the slot's length are not
  copied; their rows (stale buffer contents) are masked out of both the
  scores and the PV product, as the partial last page's rows are;
* the caller prunes the grid itself by slicing the table to a host-known
  bound on the deepest live slot's page count (see ops.paged_attn /
  the engine's page-count bucketing) — pages past *every* slot's length
  are never launched, and no table column at or past that width is read.

Pages are stored head-major so that one page copy, ``pool[page, h]``, is
a ``[page_size, D]`` tile with the page's token rows on sublanes and D on
lanes.  Mosaic copies only whole lane rows of a lane-tiled pool, so where
``D`` divides 128 the kernel reads the pool as ``[N, Hkv, page_size / f,
f * D]`` with ``f = 128 / D`` tokens to a row (:func:`lane_pack`).  Query
row ``c * Gp + g`` (``Gp``: G rounded up to 8) then holds ``q[g]`` in lane
group ``c`` and zeros elsewhere, scores only that group's keys, and keeps
its own flash accumulator; the last step merges the ``f`` partial
softmaxes of each query head.  At ``f = 1`` this is the plain
``[G, ppb * page_size]`` walk.

Launch geometry.  ``pages_per_block`` is derived from the call's shapes
by :func:`default_pages_per_block` unless the caller (or a tuned entry,
see :mod:`autotune`) names one.  ``grid_order`` picks which of the two
outer grid axes is major — ``"bh"`` walks slots outermost, ``"hb"`` walks
KV heads outermost (better pool-page locality when slots share prefix
pages).  The block axis always stays innermost: the flash accumulator
scratch is carried across grid steps and must see a slot-head's whole
walk contiguously, and every axis is sequential ("arbitrary") because
the prefetch chain runs across steps.  Either order visits the same
blocks with the same per-(slot, head) accumulation sequence, so outputs
are bit-identical between orders at one ``pages_per_block``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GRID_ORDERS = ("bh", "hb")     # batch-major / head-major outer walk
LANES = 128                    # lanes of a vreg row
SUBLANES = 8                   # f32 rows of a vreg tile
BLOCK_KEYS = 512               # keys one grid step walks by default


def default_pages_per_block(ps: int, p_cap: int) -> int:
    """Pages one grid step walks for pages of ``ps`` tokens under a table
    sliced to ``p_cap`` pages: ``BLOCK_KEYS`` keys' worth, never more
    than the table holds (a larger block would only walk dead pages).
    On a v5e the kernel's time fell with every doubling of the block
    from 1 to 32 pages of 16, at head_dim 64 and 128 alike
    (``scripts/decode_block_sweep.py``), so the rule ignores head_dim."""
    return max(1, min(p_cap, BLOCK_KEYS // ps))


def lane_pack(ps: int, d: int) -> int:
    """Tokens one buffer row holds: ``LANES // d`` when ``d`` divides a
    lane row and the page splits into whole rows, else 1.  A page copy
    must span whole lane rows — Mosaic refuses a DMA of a ``[ps, 64]``
    slice of a lane-tiled pool — so a ``d = 64`` page is copied as its
    ``[ps / 2, 128]`` view, two tokens to a row."""
    f = LANES // d if d < LANES and LANES % d == 0 else 1
    return f if ps % f == 0 else 1


def _axes(grid_order: str) -> tuple[int, int]:
    """(batch_axis, head_axis) grid positions for ``grid_order``."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {GRID_ORDERS}, "
                         f"got {grid_order!r}")
    return (0, 1) if grid_order == "bh" else (1, 0)


def _make_kernel(*, ps: int, ppb: int, p_cap: int, f: int, gp: int, d: int,
                 scale: float, b_axis: int):
    bk = ppb * ps                    # keys per block
    br = bk // f                     # buffer rows per block

    def kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
               k_buf, v_buf, k_sem, v_sem, state, m_ref, l_ref, acc_ref):
        i0, i1, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        n1 = pl.num_programs(1)
        n_pairs = pl.num_programs(0) * n1
        bi = (i0, i1)[b_axis]
        h = (i0, i1)[1 - b_axis]

        def n_live(b):
            """Live tokens of slot ``b`` inside the sliced table."""
            return jnp.minimum(len_ref[b], p_cap * ps)

        def copies(b, hh, jj, slot, fn):
            """Apply ``fn`` to the K and V page copies of block ``jj`` of
            (slot ``b``, head ``hh``) into buffer half ``slot``: one per
            live page of the block, none past the slot's length."""
            cnt = jnp.minimum((n_live(b) + ps - 1) // ps - jj * ppb, ppb)

            def body(i, carry):
                page = tbl_ref[b, jj * ppb + i]
                fn(pltpu.make_async_copy(k_hbm.at[page, hh],
                                         k_buf.at[slot, i], k_sem.at[slot]))
                fn(pltpu.make_async_copy(v_hbm.at[page, hh],
                                         v_buf.at[slot, i], v_sem.at[slot]))
                return carry
            jax.lax.fori_loop(0, cnt, body, 0)

        def next_live():
            """(found, b, h, block) of the next live grid step after this
            one: the next block of this slot-head, else block 0 of the
            next (outer, middle) pair whose slot holds any token."""
            same = (j + 1) * bk < n_live(bi)

            def split(idx):
                return idx // n1, idx % n1

            def empty(idx):
                b = split(jnp.minimum(idx, n_pairs - 1))[b_axis]
                return jnp.logical_and(idx < n_pairs, len_ref[b] <= 0)
            nxt = split(jax.lax.while_loop(empty, lambda x: x + 1,
                                           i0 * n1 + i1 + 1))
            found = jnp.logical_or(same, nxt[0] < pl.num_programs(0))
            return (found, jnp.where(same, bi, nxt[b_axis]),
                    jnp.where(same, h, nxt[1 - b_axis]),
                    jnp.where(same, j + 1, 0))

        @pl.when((i0 == 0) & (i1 == 0) & (j == 0))
        def _():
            state[0] = 0                 # buffer half of the next live block
            state[1] = 0                 # were its copies started already?

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        ln = n_live(bi)

        @pl.when(j * bk < ln)
        def _():
            cur = state[0]

            @pl.when(state[1] == 0)
            def _():
                copies(bi, h, j, cur, lambda c: c.start())

            found, nb, nh, nj = next_live()

            @pl.when(found)
            def _():
                copies(nb, nh, nj, 1 - cur, lambda c: c.start())
            state[0] = 1 - cur
            state[1] = found.astype(jnp.int32)

            copies(bi, h, j, cur, lambda c: c.wait())
            # buffer row r, lane group c holds key base + r * f + c; score
            # row c * gp + g is query g against lane group c only
            k = k_buf[cur].reshape(br, f * d)
            v = v_buf[cur].reshape(br, f * d)
            scores = jax.lax.dot_general(
                q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [f*gp, br]
            base = j * bk
            s_shape = (f * gp if f > 1 else 1, br)
            s_key = (base + f * jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
                     + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0) // gp)
            v_shape = (br, f * d if f > 1 else 1)
            v_key = (base + f * jax.lax.broadcasted_iota(jnp.int32, v_shape, 0)
                     + jax.lax.broadcasted_iota(jnp.int32, v_shape, 1) // d)
            scores = jnp.where(s_key < ln, scores, -1e30)
            m_prev = m_ref[...]                        # [f*gp, 1]
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + pexp.sum(axis=-1, keepdims=True)
            # keys past the length hold stale buffer contents: zero them so
            # a non-finite leftover cannot reach the sum through 0 * x
            v = jnp.where(v_key < ln, v.astype(jnp.float32), 0.0)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            # merge the f lane groups' partial softmaxes (f = 1: just one)
            rows = [pl.ds(c * gp, gp) for c in range(f)]
            ms = [m_ref[r] for r in rows]
            m = functools.reduce(jnp.maximum, ms)
            w = [jnp.exp(mc - m) for mc in ms]
            l = sum(wc * l_ref[r] for wc, r in zip(w, rows))
            acc = sum(wc * acc_ref[r, pl.ds(c * d, d)]
                      for c, (wc, r) in enumerate(zip(w, rows)))
            o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    return kernel


def paged_attn_kernel(q: jnp.ndarray, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, table: jnp.ndarray,
                      lengths: jnp.ndarray, *,
                      interpret: bool = True,
                      grid_order: str = "bh",
                      pages_per_block: int | None = None) -> jnp.ndarray:
    """q: [B, Hkv, G, D]; k_pages/v_pages: [N, Hkv, ps, D] pooled pages;
    table: [B, P] int32 physical page per (slot, logical page) — every
    entry must be < N (callers clamp sentinels); lengths: [B] int32.
    ``grid_order`` picks the outer grid majorness and ``pages_per_block``
    the pages one grid step walks (None: :func:`default_pages_per_block`;
    see the module docstring)."""
    b, hkv, g, d = q.shape
    n, _, ps, _ = k_pages.shape
    p_max = table.shape[1]
    if pages_per_block is None:
        pages_per_block = default_pages_per_block(ps, p_max)
    if pages_per_block <= 0:
        raise ValueError(f"pages_per_block must be positive, "
                         f"got {pages_per_block}")
    ppb = min(pages_per_block, p_max)
    f = lane_pack(ps, d)
    gp = g if f == 1 else -(-g // SUBLANES) * SUBLANES
    if f > 1:
        # lane group c of query row c * gp + g holds q[g]; the rest is 0
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        q = jnp.concatenate(
            [jnp.pad(q, ((0, 0),) * 3 + ((c * d, (f - 1 - c) * d),))
             for c in range(f)], axis=2)
        k_pages = k_pages.reshape(n, hkv, ps // f, f * d)
        v_pages = v_pages.reshape(n, hkv, ps // f, f * d)
    b_axis, h_axis = _axes(grid_order)
    grid = [0, 0, pl.cdiv(p_max, ppb)]
    grid[b_axis], grid[h_axis] = b, hkv
    grid = tuple(grid)

    def qo_map(i0, i1, j, tbl, ln):
        return ((i0, i1)[b_axis], (i0, i1)[h_axis], 0, 0)

    rows = f * gp
    buf = (2, ppb, ps // f, f * d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, f * d), qo_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d), qo_map),
        scratch_shapes=[pltpu.VMEM(buf, k_pages.dtype),
                        pltpu.VMEM(buf, v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, f * d), jnp.float32)],
    )
    out = pl.pallas_call(
        _make_kernel(ps=ps, ppb=ppb, p_cap=p_max, f=f, gp=gp, d=d,
                     scale=1.0 / math.sqrt(d), b_axis=b_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret)(table, lengths, q, k_pages, v_pages)
    return out[:, :, :g]

"""Paged GQA prefill attention (flash-prefill over a page table).

The suffix-only prefill regime (PR 3's radix cache, and chunked prefill on
top of it): a block of ``Lq`` new prompt tokens per slot, sitting at a
per-slot absolute depth ``q_offset[b]`` (its resident cached-prefix /
already-prefilled length), attends causally over everything below it —
shared prefix pages, earlier chunks and the block's own K/V, all resident
in the pooled head-major allocation stacked over layers, ``[L, n_pages,
Hkv, page_size / f, f * D]`` (:func:`kernel.page_shape`), and named by the
``[B, max_pages]`` table.  The call reads layer ``layer`` of the stack.
Unlike :mod:`kernel` (one query row, pure memory-bound), the query block
here re-uses every fetched page across ``Lq * G`` rows, so the kernel is
the compute-bound sibling: same page walk, fatter matmuls.

Layout mirrors the decode kernel.  The page table, per-slot query offsets,
per-slot live lengths and the layer are scalar-prefetched, so the
BlockSpec index map for grid step ``(b, h, r, p)`` redirects the K/V DMA
to physical page ``(layer, table[b, p])`` — the gather costs nothing
extra.  Queries are pre-folded to ``[B, Hkv, Lq * G, D]`` (row ``r`` is
query token ``r // G``, group member ``r % G``) so the block keeps D on
the 128-lane axis and the fused (query, group) rows on sublanes; the flash
accumulator (m, l, acc) is staged in VMEM across the page walk.

Packed pages.  Where ``f = 128 / D`` tokens share a stored row (D 64:
row ``i`` holds tokens ``2i`` and ``2i + 1``), the kernel reads the page
as stored, ``[page_size / f, f * D]``, and never relays it out.  The
query block is repeated in each of the ``f`` lane groups; lane group
``c`` of the page, the rest zeroed, scores tokens ``f i + c`` as one
``[rows, page_size / f]`` tile with its own causal positions, and the
``f`` tiles share one online-softmax update.  Each tile's PV product
lands in its own lane group of an ``[rows, f * D]`` accumulator, whose
lane groups the last page step adds.

Command skipping (§5.1.2) at page granularity, same two levels as decode:

* inside the kernel, ``pl.when(page_base < kv_len)`` makes every page past
  a slot's live depth a no-op (the accumulator carries through) and the
  dead page's DMA is redirected to the slot's first page, so no fresh HBM
  line is touched;
* causality adds a third skip decode does not have: a page strictly above
  *every* query row of the block (``page_base > q_offset + top_row // G``)
  is dead too — with chunked prefill most of the table is either below the
  chunk (prefix: mask-free full compute) or above it (skipped), so the
  per-chunk work stays O(depth), not O(table width);
* the caller prunes the grid by slicing the table to the page-count
  bucket, exactly like the decode path.

Tunable launch geometry (see :mod:`autotune`):

* ``block_rows`` tiles the fused ``Lq * G`` sublane axis: instead of one
  block of every query row, the grid grows a row-block axis of
  ``Lq * G // block_rows`` steps, each staging a ``[block_rows, D]``
  query block and its own flash accumulator across the page walk.
  Smaller row blocks shrink the VMEM working set and let the causal
  top-skip fire per row block (a deep row block never pays for pages
  only the shallow rows need), at the cost of re-walking the pages once
  per block.  An explicit ``block_rows`` must divide ``Lq * G``; per
  query row the accumulation sequence over pages is unchanged, so
  outputs are numerically equivalent — but not guaranteed bit-identical
  on every backend, because XLA may lower the block matmuls differently
  by shape (CPU interpret does, by ulps).  The autotuner parity-gates
  candidates against the default shape and discards non-exact ones, so
  *tuned* configs are always bit-exact on the backend that tuned them.
  The default (:func:`default_block_rows`) keeps every row in one block
  while that block's scoped-VMEM footprint (:func:`vmem_bytes`) fits
  half the chip's limit; past it, it takes the fewest row blocks that
  fit, each a multiple of ``ROW_TILE``, and the grid rounds up — the
  tail block's rows past ``Lq * G`` are computed (rows are independent)
  and their writes dropped.
* ``grid_order`` picks the outer-axis majorness exactly as in the decode
  kernel (``"bh"`` slot-major, ``"hb"`` head-major).  The row-block and
  page axes always stay innermost, pages last — the accumulator scratch
  must see one (slot, head, row-block)'s full page walk contiguously.

The fully-masked-row hazard of flash attention (a row whose max stays
``-inf`` would normalize garbage) cannot arise here: page 0 holds key
position 0, which every query row ``q_offset + t >= 0`` may attend to, so
after the first live page every row's running max is finite.  Rows of a
slot with ``kv_len == 0`` never enter compute and produce zeros.
"""
from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.hwspec import DEFAULT_TPU
from .kernel import GRID_ORDERS, _axes, page_geometry

# default row blocks past one block are multiples of the bf16 sublane tile
# (16 rows; the f32 tile of 8 divides it), as Mosaic requires of a block
# that does not span the whole row axis
ROW_TILE = 16


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def vmem_bytes(rows: int, *, d: int, ps: int, q_itemsize: int,
               kv_itemsize: int) -> int:
    """Scoped-VMEM bytes one grid step of a ``rows``-row block stages,
    counting Mosaic's padding of every minor dimension to 128 lanes: the
    q and o blocks and the two K/V page blocks (each double buffered);
    the (m, l) scratches — ``(rows, 1)`` f32, so 128 lanes each — and the
    f32 accumulator; and the 32-bit temporaries of one page step: four
    ``(rows, 1)`` columns (query positions, new running max, its
    correction, row sums), the scores and probabilities ``[rows, ps]``,
    and the PV product and rescaled accumulator ``[rows, D]``.

    Checked against the v5e compiler at D=64, ps=16, bf16: a launch of
    two 2560-row blocks compiled, one of six was refused, and 2816-row
    blocks were refused — the compiler may also place the kernel's
    operands or result in VMEM, which this model cannot see.  So
    :func:`default_block_rows` budgets half the scoped limit (at most
    1248 rows there), which compiled at every Lq tried from 5 to 2048."""
    blocks = 2 * (2 * rows * _lanes(d) * q_itemsize
                  + 2 * ps * _lanes(d) * kv_itemsize)
    scratch = 4 * rows * (2 * 128 + _lanes(d))
    temps = 4 * rows * (4 * 128 + 2 * _lanes(ps) + 2 * _lanes(d))
    return blocks + scratch + temps


def default_block_rows(lg: int, *, d: int, ps: int, q_itemsize: int,
                       kv_itemsize: int,
                       limit: int = DEFAULT_TPU.vmem_bytes // 2) -> int:
    """The default row block for ``lg`` fused rows: all of them when one
    block fits ``limit`` (a block spanning the whole axis is legal at
    any row count), else the fewest blocks that fit, each rounded up to
    a multiple of ``ROW_TILE``; the last block may then overhang the row
    axis, and its rows past the end are computed and never written."""
    kw = dict(d=d, ps=ps, q_itemsize=q_itemsize, kv_itemsize=kv_itemsize)
    fixed = vmem_bytes(0, **kw)
    per_row = vmem_bytes(1, **kw) - fixed
    max_rows = max(ROW_TILE,
                   (limit - fixed) // per_row // ROW_TILE * ROW_TILE)
    if lg <= max_rows:
        return lg
    n_blocks = -(-lg // max_rows)
    return -(-lg // (n_blocks * ROW_TILE)) * ROW_TILE


def _make_kernel(ps: int, f: int, d: int, g: int, scale: float,
                 b_axis: int):
    def kernel(tbl_ref, off_ref, len_ref, lay_ref, q_ref, k_ref, v_ref,
               o_ref, m_ref, l_ref, acc_ref):
        bi = pl.program_id(b_axis)
        r = pl.program_id(2)
        p = pl.program_id(3)
        np_ = pl.num_programs(3)
        off = off_ref[bi]
        ln = len_ref[bi]
        br = m_ref.shape[0]               # rows of this block (<= Lq * G)

        @pl.when(p == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        base = p * ps
        # fused row r*br + j is query token (r*br + j) // g at absolute
        # position off + that token index
        row0 = r * br
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
        qpos = off + rows // g                                # [br, 1]
        # stored row i of the page holds keys base + f * i + c, c < f
        kpos = base + f * jax.lax.broadcasted_iota(jnp.int32,
                                                   (1, ps // f), 1)

        # page-granular command skipping, both ends of the causal window:
        # pages past the slot's live depth AND pages strictly above every
        # query row of this row block do no compute (their DMA was
        # redirected to the slot's first page, so no new HBM line was
        # pulled either)
        @pl.when((base < ln) & (base <= off + (row0 + br - 1) // g))
        def _():
            q = q_ref[0, 0]                  # [br, f * D]
            k = k_ref[0, 0].astype(q.dtype)  # [ps / f, f * D]
            v = v_ref[0, 0].astype(jnp.float32)
            if f > 1:
                group = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) // d
            scores = []
            for c in range(f):
                kc = k if f == 1 else jnp.where(group == c, k, 0)
                s = jax.lax.dot_general(
                    q, kc, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                live = (kpos + c <= qpos) & (kpos + c < ln)   # [br, ps/f]
                scores.append(jnp.where(live, s, -1e30))
            m_prev = m_ref[...]              # [br, 1]
            m_new = functools.reduce(
                jnp.maximum, [s.max(axis=-1, keepdims=True) for s in scores],
                m_prev)
            pexp = [jnp.exp(s - m_new) for s in scores]
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + functools.reduce(
                operator.add, [pc.sum(axis=-1, keepdims=True) for pc in pexp])
            acc_ref[...] = acc_ref[...] * corr + functools.reduce(
                operator.add, [jax.lax.dot_general(
                    pc.astype(jnp.float32),
                    v if f == 1 else jnp.where(group == c, v, 0.0),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for c, pc in enumerate(pexp)])
            m_ref[...] = m_new

        @pl.when(p == np_ - 1)
        def _():
            acc = functools.reduce(
                operator.add, [acc_ref[:, pl.ds(c * d, d)] for c in range(f)])
            o_ref[0, 0] = (acc / jnp.maximum(l_ref[...], 1e-30)
                           ).astype(o_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("g", "interpret",
                                             "block_rows", "grid_order"))
def paged_prefill_attn_kernel(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, table: jnp.ndarray,
                              q_offset: jnp.ndarray, kv_len: jnp.ndarray,
                              layer: jnp.ndarray,
                              *, g: int, interpret: bool = True,
                              block_rows: int | None = None,
                              grid_order: str = "bh") -> jnp.ndarray:
    """q: [B, Hkv, Lq * G, D] fused query rows (row ``r`` = token ``r // g``
    of group member ``r % g``); k_pages/v_pages: the stacked pools as
    stored, [L, N, Hkv, ps / f, f * D]; table: [B, P] int32, every entry
    < N (callers clamp sentinels); q_offset/kv_len: [B] int32 per-slot
    depth of the query block and total live KV length (``q_offset + Lq``
    for a suffix prefill); layer: [1] int32, the layer of the stack.
    ``block_rows`` (must divide ``Lq * G``; default:
    :func:`default_block_rows`, whose tail block may overhang) and ``grid_order`` tune the launch geometry — outputs are
    numerically equivalent across valid settings; bit-exactness per
    backend is verified by the autotuner (see module docstring)."""
    b, hkv, lg, d = q.shape
    ps, f = page_geometry(k_pages, d)
    rows, lanes = k_pages.shape[-2:]
    p_max = table.shape[1]
    if block_rows is None:
        br = default_block_rows(
            lg, d=d, ps=ps, q_itemsize=q.dtype.itemsize,
            kv_itemsize=k_pages.dtype.itemsize)
    else:
        br = int(block_rows)
        if br <= 0 or lg % br:
            raise ValueError(f"block_rows={block_rows} must divide the "
                             f"fused query-row count Lq*G={lg}")
    b_axis, h_axis = _axes(grid_order)
    grid = [0, 0, pl.cdiv(lg, br), p_max]
    grid[b_axis], grid[h_axis] = b, hkv
    grid = tuple(grid)

    def kv_map(i0, i1, r, p, tbl, off, ln, lay):
        bi, h = (i0, i1)[b_axis], (i0, i1)[h_axis]
        # dead pages (past the live depth, or above the whole row block)
        # re-fetch the slot's first page instead of pulling a fresh line
        base = p * ps
        dead = (base >= ln[bi]) | (base > off[bi] + (r * br + br - 1) // g)
        pg = jnp.where(dead, tbl[bi, 0], tbl[bi, p])
        return (lay[0], pg, h, 0, 0)

    def q_map(i0, i1, r, p, tbl, off, ln, lay):
        bi, h = (i0, i1)[b_axis], (i0, i1)[h_axis]
        return (bi, h, r, 0)

    if f > 1:
        # each lane group of a stored row scores against its own copy
        q = jnp.tile(q, (1, 1, 1, f))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, br, f * d), q_map),
            pl.BlockSpec((None, 1, 1, rows, lanes), kv_map),
            pl.BlockSpec((None, 1, 1, rows, lanes), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, br, d), q_map),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32),
                        pltpu.VMEM((br, 1), jnp.float32),
                        pltpu.VMEM((br, f * d), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel(ps, f, d, g, 1.0 / math.sqrt(d), b_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, lg, d), q.dtype),
        interpret=interpret)(table, q_offset, kv_len, layer, q, k_pages,
                             v_pages)

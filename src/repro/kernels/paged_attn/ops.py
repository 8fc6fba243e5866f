"""Public paged decode-attention op.

``paged_attn`` is the page-table analogue of ``decode_attn``: one-token
GQA queries against K/V pages gathered through a per-slot page table, with
per-slot live lengths.  Grid pruning is shape-driven — callers slice the
table to a host-known bound on the deepest live slot's page count (the
serving engine's page-count bucketing), so the kernel grid *is* the pruned
page count; per-slot skipping inside the kernel handles the rest.

Pools are the model's stacked ``[L, N, Hkv, page_size / f, f * D]``
arrays as stored (:func:`kernel.page_shape`); every op takes the layer
to read as its ``layer`` argument (default 0), so a caller inside the
layer loop passes the whole stack and slices nothing.

Routing (kernel vs XLA gather, interpret on/off) reuses the
``DecodeAttnPolicy`` from :mod:`repro.kernels.decode_attn` — the decision
is about the backend, not about which cache layout is in play.

Sentinel handling: unallocated table entries are ``>= n_pages`` (the
pool's OOB id, chosen so cache *scatters* through them drop).  For reads
they are clamped to a valid page here, once, and masked by ``lengths``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import default_pages_per_block, page_geometry, paged_attn_kernel
from .prefill_kernel import paged_prefill_attn_kernel
from .ref import gather_pages


class PagedAttnTelemetry:
    """Host-side timing hooks for the paged-attention ops.

    Disabled by default, in which case every op takes a single
    ``if not enabled`` branch and nothing else — no timing, no device
    sync, no allocation.  When enabled, each public op records under a
    ``(op, route)`` key (op in ``decode`` / ``prefill`` / ``verify``,
    route in ``kernel`` / ``xla``):

    * ``calls`` — total invocations;
    * ``traced_calls`` — the subset seen under a jax trace (inside
      ``jit`` / ``scan``), where the op runs once per *compile*, not per
      step, and wall time would be trace time — so those calls are
      counted but never timed or synced;
    * ``tokens`` — query-token volume (B × Lq), from static shapes so
      it is meaningful for traced calls too;
    * ``wall_s`` — eager-call wall time, measured around a
      ``block_until_ready`` on the op's output.  Only eager calls pay
      this sync; jitted serving paths are untouched by design.

    Roofline accounting (live since PR 8) rides on the same hooks: each
    call also contributes analytic traffic estimates from its *static*
    shapes plus the concrete page table/length metadata when available
    (eager calls — under trace the lengths are abstract and the full
    sliced table width is assumed live):

    * ``bytes`` — physical HBM traffic: live K/V pages touched (dead
      pages the kernel's page walk skips are subtracted) × page extent ×
      dtype width × 2, plus Q read + O write + table reads;
    * ``flops`` — attention math, 4 × Hq × D per causally-visible
      (query, kv) pair;
    * ``onchip_bytes`` — logical K/V reads served by on-chip reuse
      (GQA group folding, query rows sharing a page) rather than HBM;
    * ``timed_bytes`` — the ``bytes`` of eager (timed) calls only, so
      ``achieved_gbps`` divides matched numerator/denominator.

    ``snapshot()`` derives ``achieved_gbps`` (timed bytes over eager
    wall time) and ``op_byte`` (flops over physical + on-chip bytes —
    the :class:`~repro.core.amenability.PrimitiveProfile` convention)
    per ``(op, route)``; :func:`amenability_reports` feeds the
    aggregates through the paper's amenability test.
    """

    def __init__(self):
        self.enabled = False
        self.stats: dict = {}

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self.stats = {}

    def _bump(self, op: str, route: str, tokens: int, *,
              traced: bool = False, wall: float = 0.0,
              mem_bytes: float = 0.0, flops: float = 0.0,
              onchip_bytes: float = 0.0) -> None:
        d = self.stats.setdefault((op, route), {
            "calls": 0, "traced_calls": 0, "tokens": 0, "wall_s": 0.0,
            "bytes": 0.0, "flops": 0.0, "onchip_bytes": 0.0,
            "timed_bytes": 0.0})
        d["calls"] += 1
        d["traced_calls"] += int(traced)
        d["tokens"] += tokens
        d["wall_s"] += wall
        d["bytes"] += mem_bytes
        d["flops"] += flops
        d["onchip_bytes"] += onchip_bytes
        if not traced:
            d["timed_bytes"] += mem_bytes

    def snapshot(self) -> dict:
        """Flat ``{"op.route": {...}}`` copy for reporting, with the
        derived roofline numbers: ``achieved_gbps`` (eager-call bytes
        over eager-call wall, 0 when nothing was timed) and ``op_byte``
        (flops over physical + on-chip bytes)."""
        out: dict = {}
        for (op, route), d in sorted(self.stats.items()):
            row = dict(d)
            row["achieved_gbps"] = (
                row["timed_bytes"] / row["wall_s"] / 1e9
                if row["wall_s"] > 0.0 else 0.0)
            denom = row["bytes"] + row["onchip_bytes"]
            row["op_byte"] = row["flops"] / denom if denom else 0.0
            out[f"{op}.{route}"] = row
        return out


_TELEMETRY = PagedAttnTelemetry()


def attn_telemetry() -> PagedAttnTelemetry:
    """The module-level :class:`PagedAttnTelemetry` instance shared by
    every op in this module."""
    return _TELEMETRY


def amenability_reports(pim=None, gpu=None) -> dict:
    """Run the paper's PIM-amenability test over the *measured* op mix.

    Aggregates the telemetry's per-``(op, route)`` roofline estimates
    into one :class:`~repro.core.amenability.PrimitiveProfile` per op
    (decode / prefill / verify, routes summed — the traffic is a
    property of the math, not the backend) and feeds each through
    :func:`~repro.core.amenability.run_test`.  This is the live
    counterpart of the static profiles in ``core``: op/byte and
    mem-ratio come from what the serving wave actually executed, dead
    pages and speculative verify rows included.

    Returns ``{op: AmenabilityReport}``; empty when telemetry recorded
    nothing (disabled, or no paged-attention calls).
    """
    from ...core.amenability import Interaction, PrimitiveProfile, run_test
    interactions = {
        # one query row, dot-reduce over its resident KV — commutative
        # page-at-a-time accumulation (flash online softmax)
        "decode": Interaction.REDUCTION,
        # chunked causal block: query rows × KV pages interact within
        # the slot's own pages — localized, co-alignable per slot
        "prefill": Interaction.LOCALIZED,
        "verify": Interaction.LOCALIZED,
    }
    agg: dict = {}
    for (op, _route), d in _TELEMETRY.stats.items():
        a = agg.setdefault(op, {"flops": 0.0, "bytes": 0.0, "onchip": 0.0})
        a["flops"] += d["flops"]
        a["bytes"] += d["bytes"]
        a["onchip"] += d["onchip_bytes"]
    reports: dict = {}
    for op, a in sorted(agg.items()):
        if a["bytes"] + a["onchip"] <= 0.0:
            continue
        profile = PrimitiveProfile(
            name=f"paged-attn/{op}",
            ops=a["flops"],
            mem_bytes=a["bytes"],
            onchip_bytes=a["onchip"],
            interaction=interactions.get(op, Interaction.IRREGULAR),
            alignable=True,
            input_dependent_locality=True,
            notes="measured mix; page-table indirection makes locality "
                  "input-dependent (which pages a slot touches is data)")
        reports[op] = run_test(profile, pim, gpu)
    return reports


def _concrete_i64(x) -> "np.ndarray | None":
    """``x`` as a host int64 vector, or None when it is abstract."""
    if x is None or isinstance(x, jax.core.Tracer):
        return None
    try:
        return np.asarray(x, dtype=np.int64).reshape(-1)
    except (TypeError, ValueError):
        return None


def _traffic(q, k_pages, table, lengths, q_offset=None) -> tuple:
    """Analytic ``(mem_bytes, flops, onchip_bytes)`` for one call.

    Physical K/V traffic counts only *live* pages — the pages the
    kernel's walk actually reads.  Decode: ``ceil(lengths[b] / ps)``
    pages per slot; prefill/verify additionally bounds the walk at the
    causal end ``q_offset[b] + Lq``.  When lengths/offsets are abstract
    (the call sits under a jax trace) the full caller-sliced table
    width is assumed live — an upper bound consistent with the grid the
    kernel was actually compiled for.

    FLOPs are 4 × Hq × D per causally-visible (query, kv) pair (QKᵀ
    and PV, 2 each).  On-chip bytes are the logical K/V reads in excess
    of the physical ones: the GQA group (G query heads per KV head) and
    the Lq query rows of a chunk re-read each resident page from
    on-chip storage, not HBM.
    """
    b = int(q.shape[0])
    lq = int(q.shape[1]) if q.ndim == 4 else 1
    hq = int(q.shape[-2])
    d = int(q.shape[-1])
    hkv, (ps, _) = int(k_pages.shape[2]), page_geometry(k_pages, d)
    p = int(table.shape[-1])
    item = jnp.dtype(k_pages.dtype).itemsize
    qitem = jnp.dtype(q.dtype).itemsize

    ln = _concrete_i64(lengths)
    off = _concrete_i64(q_offset) if q_offset is not None else None
    if ln is not None:
        ln = np.broadcast_to(ln, (b,)).astype(np.int64)
    if ln is None or (q_offset is not None and off is None):
        # abstract metadata: the whole sliced table is assumed live
        kv_end = np.full((b,), p * ps, dtype=np.int64)
        visible = float(b * lq * p * ps)
    elif q_offset is None:
        # decode: one query per slot sees its whole resident context
        kv_end = np.minimum(ln, p * ps)
        visible = float(kv_end.sum())
    else:
        # prefill/verify: causal suffix rows at absolute depths
        off = np.broadcast_to(off, (b,)).astype(np.int64)
        kv_end = np.minimum(np.minimum(ln, off + lq), p * ps)
        i = np.arange(lq, dtype=np.int64)[None, :]
        vis = np.minimum(off[:, None] + i + 1, ln[:, None])
        visible = float(np.clip(vis, 0, p * ps).sum())
    live_pages = np.minimum((np.maximum(kv_end, 0) + ps - 1) // ps, p)
    kv_phys = float(live_pages.sum()) * ps * hkv * d * item * 2
    mem = kv_phys + 2.0 * b * lq * hq * d * qitem + b * p * 4.0
    flops = 4.0 * hq * d * visible
    kv_logical = visible * hq * d * item * 2
    return mem, flops, max(0.0, kv_logical - kv_phys)


def _recorded(op: str, route: str, q: jnp.ndarray, fn, *args,
              traffic: tuple = (0.0, 0.0, 0.0), **kw):
    """Run ``fn(*args, **kw)``, attributing it to ``(op, route)``.

    Token volume comes from ``q``'s static shape (B × Lq; Lq = 1 for
    [B, H, D] decode queries).  Traced calls are counted but not timed:
    a ``block_until_ready`` under trace would be wrong twice over (it
    measures tracing, and it would land inside the caller's jit).
    ``traffic`` is the caller's :func:`_traffic` estimate, accumulated
    alongside."""
    tel = _TELEMETRY
    tokens = int(q.shape[0]) * (int(q.shape[1]) if q.ndim == 4 else 1)
    mem, flops, onchip = traffic
    if isinstance(q, jax.core.Tracer):
        tel._bump(op, route, tokens, traced=True, mem_bytes=mem,
                  flops=flops, onchip_bytes=onchip)
        return fn(*args, **kw)
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    tel._bump(op, route, tokens, wall=time.perf_counter() - t0,
              mem_bytes=mem, flops=flops, onchip_bytes=onchip)
    return out


def _clamp_table(table: jnp.ndarray, n_pages: int) -> jnp.ndarray:
    return jnp.minimum(table.astype(jnp.int32), n_pages - 1)


def _tuned_launch(op: str, q, k_pages, *, lg: int) -> dict:
    """The active policy's tuned launch config for this call shape
    (``{}`` on a miss / tuned loading disabled).  Shapes are static even
    under trace, so resolution works at trace time."""
    from ..decode_attn import active_policy
    d = int(q.shape[-1])
    return active_policy().tuned_config(
        op, hq=int(q.shape[-2]), hkv=int(k_pages.shape[2]), d=d,
        page_size=page_geometry(k_pages, d)[0], lg=lg) or {}


def decode_launch(*, hq: int, hkv: int, d: int, page_size: int, p_cap: int,
                  grid_order: str | None = None,
                  pages_per_block: int | None = None) -> tuple[str, int]:
    """The decode kernel's ``(grid_order, pages_per_block)`` for a call
    of this shape under a table sliced to ``p_cap`` pages: an explicit
    value wins, then the active policy's tuned entry (:mod:`autotune`),
    then the defaults (``"bh"``, :func:`kernel.default_pages_per_block`).
    The block never exceeds the table, so each page-cap bucket compiles
    one program.  Shapes are static even under trace, so this resolves
    at trace time — and on the host, where the scheduler counts the
    pages the kernel walks."""
    if grid_order is None or pages_per_block is None:
        from ..decode_attn import active_policy
        cfg = active_policy().tuned_config(
            "decode", hq=hq, hkv=hkv, d=d, page_size=page_size,
            lg=hq // hkv) or {}
        if grid_order is None:
            grid_order = cfg.get("grid_order", "bh")
        if pages_per_block is None:
            pages_per_block = (cfg.get("pages_per_block")
                               or default_pages_per_block(page_size, p_cap))
    return grid_order, min(pages_per_block, p_cap)


@functools.partial(jax.jit, static_argnames=("interpret", "grid_order",
                                             "pages_per_block"))
def _paged_attn_jit(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, table: jnp.ndarray,
                    lengths: jnp.ndarray, layer: jnp.ndarray, *,
                    interpret: bool = True,
                    grid_order: str = "bh",
                    pages_per_block: int | None = None) -> jnp.ndarray:
    b, hq, d = q.shape
    hkv = k_pages.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    tbl = _clamp_table(table, k_pages.shape[1])
    ln = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    out = paged_attn_kernel(qg, k_pages, v_pages, tbl, ln, _layer(layer),
                            interpret=interpret, grid_order=grid_order,
                            pages_per_block=pages_per_block)
    return out.reshape(b, hq, d)


def _layer(layer) -> jnp.ndarray:
    """The layer index as the kernels' [1] int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def paged_attn(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
               table: jnp.ndarray, lengths: jnp.ndarray, layer=0, *,
               interpret: bool = True,
               grid_order: str | None = None,
               pages_per_block: int | None = None) -> jnp.ndarray:
    """q: [B, Hq, D] one-token queries; k_pages/v_pages: stacked pools as
    stored, [L, N, Hkv, ps / f, f * D]; table: [B, P] int32; slot b
    attends over the first ``lengths[b]`` tokens of its pages of layer
    ``layer`` in table order.  ``grid_order`` / ``pages_per_block`` left
    None resolve by :func:`decode_launch`."""
    d = int(q.shape[-1])
    grid_order, pages_per_block = decode_launch(
        hq=int(q.shape[-2]), hkv=int(k_pages.shape[2]), d=d,
        page_size=page_geometry(k_pages, d)[0], p_cap=int(table.shape[-1]),
        grid_order=grid_order, pages_per_block=pages_per_block)
    kw = dict(interpret=interpret, grid_order=grid_order,
              pages_per_block=pages_per_block)
    if not _TELEMETRY.enabled:
        return _paged_attn_jit(q, k_pages, v_pages, table, lengths, layer,
                               **kw)
    return _recorded("decode", "kernel", q, _paged_attn_jit,
                     q, k_pages, v_pages, table, lengths, layer,
                     traffic=_traffic(q, k_pages, table, lengths), **kw)


def paged_attn_xla(q: jnp.ndarray, k_pages: jnp.ndarray,
                   v_pages: jnp.ndarray, table: jnp.ndarray,
                   lengths: jnp.ndarray, layer=0) -> jnp.ndarray:
    """Gather-then-attend fallback: identical math on the XLA path (used
    off-TPU where the Pallas interpreter would sit in the hot loop)."""
    if _TELEMETRY.enabled:
        return _recorded("decode", "xla", q, _paged_attn_xla_impl,
                         q, k_pages, v_pages, table, lengths, layer,
                         traffic=_traffic(q, k_pages, table, lengths))
    return _paged_attn_xla_impl(q, k_pages, v_pages, table, lengths, layer)


def _paged_attn_xla_impl(q, k_pages, v_pages, table, lengths, layer):
    from ..decode_attn.ref import decode_attn_ref
    d = q.shape[-1]
    k = gather_pages(k_pages, table, layer, d)
    v = gather_pages(v_pages, table, layer, d)
    return decode_attn_ref(q, k, v, lengths).astype(q.dtype)


def paged_prefill_attn_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, table: jnp.ndarray,
                              q_offset: jnp.ndarray, kv_len: jnp.ndarray,
                              layer=0, *,
                              interpret: bool = True,
                              block_rows: int | None = None,
                              grid_order: str = "bh") -> jnp.ndarray:
    """The Pallas flash-prefill path (see :mod:`prefill_kernel`): q
    [B, L, Hq, D] causal suffix queries at per-slot depths ``q_offset``
    [B], over layer ``layer`` of the stacked pools, masked to ``kv_len``.
    Queries are folded to [B, Hkv, L * G, D] so the kernel's block rows
    fuse (token, group) and D stays on the lane axis; the kernel casts
    each K/V page to the query dtype (the pool may hold a narrower
    storage dtype).  ``block_rows`` / ``grid_order`` pass straight to the
    kernel's launch geometry — tuned-shape resolution happens in
    :func:`paged_prefill_attn`, not here."""
    b, lq, hq, d = q.shape
    hkv = k_pages.shape[2]
    g = hq // hkv
    qf = q.reshape(b, lq, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(b, hkv, lq * g, d)
    tbl = _clamp_table(table, k_pages.shape[1])
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1),
                           (b,))
    ln = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    out = paged_prefill_attn_kernel(qf, k_pages, v_pages, tbl, off, ln,
                                    _layer(layer), g=g, interpret=interpret,
                                    block_rows=block_rows,
                                    grid_order=grid_order)
    return out.reshape(b, hkv, lq, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(b, lq, hq, d)


def paged_prefill_attn(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, table: jnp.ndarray,
                       q_offset: jnp.ndarray,
                       kv_len: jnp.ndarray, layer=0, *,
                       grid_order: str | None = None,
                       block_rows: int | None = None,
                       _op: str | None = None) -> jnp.ndarray:
    """Prefill-attention through the page table: multi-token causal GQA
    queries ``q`` [B, L, Hq, D] at per-slot depths ``q_offset`` [B] over
    layer ``layer`` of the stacked pools, masked to each slot's
    ``kv_len``.

    This is the suffix-only prefill path: a joining slot whose prompt
    prefix is already resident (shared prefix pages mapped by the radix
    cache, or written by an earlier prefill chunk) computes attention for
    *only its uncached suffix*, with the gather reading the resident pages
    in place — the prefix KV is neither recomputed nor restored.  Sentinel
    table entries clamp inside the gather and are masked by ``kv_len``.

    Routing follows the same ``DecodeAttnPolicy`` as the decode ops: on
    real TPU backends (or ``mode="kernel"``) this runs the Pallas
    flash-prefill kernel (:mod:`prefill_kernel`), whose page walk skips
    dead pages at both ends of the causal window; elsewhere the XLA
    gather-then-attend reference keeps the interpreter out of the serving
    hot loop.  MLA callers (no per-head pages to walk) stay on the ref.

    ``grid_order`` / ``block_rows`` left None resolve through the active
    policy's tuned-shape cache for this call's (backend, op, geometry)
    key — defaults when no entry matches; explicit values always win
    (the autotuner drives the sweep through them).  The XLA route has no
    launch geometry, so both knobs are ignored there.
    """
    from ..decode_attn import active_policy
    pol = active_policy()
    op = _op or ("decode" if q.shape[1] == 1 else "prefill")
    if pol.kernel_wanted():
        if grid_order is None or block_rows is None:
            g = int(q.shape[2]) // int(k_pages.shape[2])
            cfg = _tuned_launch(op, q, k_pages, lg=int(q.shape[1]) * g)
            if grid_order is None:
                grid_order = cfg.get("grid_order", "bh")
            if block_rows is None:
                block_rows = cfg.get("block_rows")
        if _TELEMETRY.enabled:
            return _recorded(op, "kernel", q, paged_prefill_attn_pallas,
                             q, k_pages, v_pages, table, q_offset, kv_len,
                             layer,
                             traffic=_traffic(q, k_pages, table, kv_len,
                                              q_offset=q_offset),
                             interpret=pol.resolve_interpret(),
                             block_rows=block_rows, grid_order=grid_order)
        return paged_prefill_attn_pallas(q, k_pages, v_pages, table,
                                         q_offset, kv_len, layer,
                                         interpret=pol.resolve_interpret(),
                                         block_rows=block_rows,
                                         grid_order=grid_order)
    from .ref import paged_prefill_attn_ref
    if _TELEMETRY.enabled:
        return _recorded(op, "xla", q, paged_prefill_attn_ref,
                         q, k_pages, v_pages, table, q_offset, kv_len, layer,
                         traffic=_traffic(q, k_pages, table, kv_len,
                                          q_offset=q_offset))
    return paged_prefill_attn_ref(q, k_pages, v_pages, table,
                                  q_offset, kv_len, layer)


def paged_verify_attn(q: jnp.ndarray, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, table: jnp.ndarray,
                      q_offset: jnp.ndarray,
                      kv_len: jnp.ndarray, layer=0, *,
                      grid_order: str | None = None,
                      block_rows: int | None = None) -> jnp.ndarray:
    """Speculative-decode **verify** attention: score a slot's current
    token plus its k drafts (``q`` [B, k+1, Hq, D]) in one call at the
    slot's decode depth ``q_offset = lengths``.

    This is *exactly* :func:`paged_prefill_attn` — a verify is a
    multi-token causal query block at absolute depth, indistinguishable
    from a suffix-prefill chunk at the kernel level — re-exported under
    its serving-side name so the contract is explicit:

    * the k+1 K/V rows were scattered at positions ``lengths .. lengths
      + k`` *before* the gather (``_paged_insert`` is position-indexed,
      scatters precede gathers per layer), so draft t attends over
      drafts 0..t-1 through the table like any resident token;
    * **rollback-safety** is a property of that position-indexed insert:
      committing fewer than k+1 tokens just means ``lengths`` advances
      past only the accepted prefix — the stale rows above it sit inside
      the slot's reserved speculation window, are never readable (the
      causal mask bounds every future read at the *new* ``lengths``),
      and the next verify's scatter overwrites them;
    * routing follows the same ``DecodeAttnPolicy``: the Pallas
      flash-prefill kernel on real TPU backends (Lq = k+1 rows fused
      with the GQA group on the sublane axis), the XLA gather ref
      elsewhere.  Nothing k-specific is compiled — one executable serves
      any draft that fits the reserved window.
    """
    return paged_prefill_attn(q, k_pages, v_pages, table, q_offset, kv_len,
                              layer, grid_order=grid_order,
                              block_rows=block_rows, _op="verify")

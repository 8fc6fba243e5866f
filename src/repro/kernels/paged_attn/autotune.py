"""Autotuning harness for the paged-attention kernel family.

The decode / prefill / verify kernels carry tunable launch geometry
(``grid_order`` on both, ``pages_per_block`` on the decode block walk,
``block_rows`` on the prefill/verify row fold — see :mod:`kernel` and
:mod:`prefill_kernel`) that until now ran on
hand-picked defaults validated only under CPU interpret.  This module
mechanizes the PrIM-style sweep the paper argues every primitive needs
before "fast as the hardware allows" claims mean anything:

1. **Enumerate** candidate configs per (backend, op, geometry):
   ``grid_order`` in ``("bh", "hb")`` for every op, plus every power of
   two up to the table width as ``pages_per_block`` for decode and every
   divisor of the fused ``Lq * G`` row count as ``block_rows`` for
   prefill/verify.
   Page size is a *geometry* axis, not a candidate axis — it changes the
   pool layout, so the CLI sweeps it as separate geometries.
2. **Prune** with an analytic score that reuses PR 8's
   :func:`repro.kernels.paged_attn.ops._traffic` roofline model:
   per-candidate physical HBM traffic (row blocks re-walk the page list,
   the causal top-skip refunds pages above each block), a sublane-
   occupancy derate on compute, and a per-grid-step dispatch charge.
   Infeasible tilings (non-divisor ``block_rows``, a page block on
   prefill, VMEM overflow) never
   run; the feasible set is ranked and cut to ``budget``.
3. **Benchmark** survivors through the existing kernel-timing hooks
   (:func:`repro.kernels.paged_attn.ops.attn_telemetry`): one untimed
   compile/warmup call, then ``reps`` eagerly-timed calls whose wall
   time, achieved GB/s and op/byte come straight off the telemetry
   snapshot.  Every candidate's output is **parity-gated** against the
   default shape's output: a candidate that is not bit-exact on this
   backend is discarded before winner selection (XLA may lower small
   row blocks with different accumulation order — ulp drift is real on
   CPU interpret), so persisted winners are bit-exact by construction.
4. **Persist** winners to a versioned JSON cache (default
   ``benchmarks/tuned_shapes.json``) keyed
   ``"<backend>|<op>|hq{H}.hkv{K}.d{D}.ps{P}"``.
   :class:`repro.kernels.decode_attn.ops.DecodeAttnPolicy` resolves the
   cache at construction time and the ops consult it per call shape;
   the ``REPRO_TUNED_SHAPES`` env var overrides the path or (set to
   ``0`` / ``off`` / ``ignore`` / ``none`` / empty) disables loading.

Cache schema (``SCHEMA == 1``)::

    {"schema": 1,
     "entries": {"cpu|decode|hq4.hkv1.d16.ps8": {
         "config": {"grid_order": "hb"},          # winner launch config
         "wall_s": ..., "default_wall_s": ...,    # provenance
         "achieved_gbps": ..., "op_byte": ...,
         "geometry": "hq4.hkv1.d16.ps8", "op": "decode"}}}

``scripts/autotune.py`` drives full sweeps; ``serve_bench.py
--autotune-compare`` runs the bounded CI tier and writes per-candidate
rows into ``BENCH_serve.json`` for ``check_bench.py`` to gate.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from ...core.hwspec import DEFAULT_TPU, TpuSpec
from .kernel import GRID_ORDERS, default_pages_per_block, pack_pages
from .prefill_kernel import default_block_rows, vmem_bytes

SCHEMA = 1
OPS = ("decode", "prefill", "verify")
ENV_VAR = "REPRO_TUNED_SHAPES"
_ENV_OFF = ("", "0", "off", "ignore", "none")
DEFAULT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, os.pardir,
    "benchmarks", "tuned_shapes.json"))
# analytic per-grid-step dispatch charge (ns).  A ranking device, not a
# measurement: it makes a tiling that quadruples the grid pay for it in
# the score, at roughly a compiled-mode launch cost.
DISPATCH_NS = 300.0


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The model/pool shape a tuned entry is keyed by.  ``lq`` is *not*
    part of the key — ``block_rows`` is sanitized against the runtime
    ``Lq * G`` at lookup time instead, so one entry serves every chunk
    length whose row count it divides."""
    hq: int
    hkv: int
    d: int
    page_size: int

    @property
    def g(self) -> int:
        return self.hq // self.hkv

    def key(self) -> str:
        return (f"hq{self.hq}.hkv{self.hkv}.d{self.d}"
                f".ps{self.page_size}")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One launch configuration.  ``block_rows=None`` means the kernel's
    default row fold (:func:`prefill_kernel.default_block_rows`; the
    only valid value for decode); ``pages_per_block=None`` the decode
    kernel's shape default (:func:`kernel.default_pages_per_block`; the
    only valid value for prefill/verify)."""
    grid_order: str = "bh"
    block_rows: int | None = None
    pages_per_block: int | None = None

    def as_dict(self) -> dict:
        cfg = {"grid_order": self.grid_order}
        if self.block_rows is not None:
            cfg["block_rows"] = self.block_rows
        if self.pages_per_block is not None:
            cfg["pages_per_block"] = self.pages_per_block
        return cfg

    def label(self) -> str:
        br = "default" if self.block_rows is None else str(self.block_rows)
        if self.pages_per_block is not None:
            return f"{self.grid_order}/br={br}/ppb={self.pages_per_block}"
        return f"{self.grid_order}/br={br}"


def entry_key(backend: str, op: str, geom: Geometry) -> str:
    return f"{backend}|{op}|{geom.key()}"


@dataclasses.dataclass
class Workload:
    """Concrete arrays for one (op, geometry) benchmark point."""
    op: str
    geom: Geometry
    q: jnp.ndarray
    k_pages: jnp.ndarray
    v_pages: jnp.ndarray
    table: jnp.ndarray
    lengths: jnp.ndarray
    q_offset: jnp.ndarray | None      # None for decode
    lq: int                           # 1 for decode

    @property
    def lg(self) -> int:
        """Fused sublane row count the kernel sees."""
        return self.geom.g if self.op == "decode" else self.lq * self.geom.g


def make_workload(op: str, geom: Geometry, *, b: int = 2, lq: int = 8,
                  pages: int = 16, seed: int = 0) -> Workload:
    """Random pooled-page workload in the shape the serving engine hands
    the kernels (mirrors ``serve_bench.roofline_probe``): a permuted page
    table, per-slot offsets at least one page deep, live lengths inside
    the sliced table."""
    if op not in OPS:
        raise ValueError(f"op must be one of {OPS}, got {op!r}")
    if pages % b:
        raise ValueError(f"pages={pages} must be divisible by b={b}")
    ps, hkv, hq, d = geom.page_size, geom.hkv, geom.hq, geom.d
    p_max = pages // b
    if op != "decode" and (p_max - 1) * ps - lq <= ps:
        raise ValueError(f"workload too small: need (pages/b - 1) * "
                         f"page_size > page_size + lq "
                         f"(pages={pages}, b={b}, ps={ps}, lq={lq})")
    rng = np.random.default_rng(seed)
    # one layer's pool, stored as the kernels read it
    kp = pack_pages(jnp.asarray(rng.standard_normal((1, pages, hkv, ps, d)),
                                jnp.float32))
    vp = pack_pages(jnp.asarray(rng.standard_normal((1, pages, hkv, ps, d)),
                                jnp.float32))
    tbl = jnp.asarray(rng.permutation(pages)[:b * p_max]
                      .reshape(b, p_max).astype(np.int32))
    if op == "decode":
        q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
        ln = jnp.asarray(rng.integers(ps, p_max * ps, size=b)
                         .astype(np.int32))
        return Workload(op, geom, q, kp, vp, tbl, ln, None, 1)
    q = jnp.asarray(rng.standard_normal((b, lq, hq, d)), jnp.float32)
    off = jnp.asarray(rng.integers(ps, (p_max - 1) * ps - lq, size=b)
                      .astype(np.int32))
    return Workload(op, geom, q, kp, vp, tbl, off + lq, off, lq)


def enumerate_candidates(op: str, lg: int | None = None,
                         pages: int | None = None) -> list[Candidate]:
    """Every launch config the kernels accept for ``op``: both grid
    orders, (decode) every power of two up to the ``pages`` of the
    sliced table as ``pages_per_block``, and (prefill/verify) every
    divisor of the fused row count as ``block_rows``.  The default
    ``Candidate()`` is always first — the pruner keeps it and the
    benchmark parity-gates against it."""
    if op == "decode":
        blocks = [1 << i for i in range(pages.bit_length())] if pages else []
        return [Candidate(o, pages_per_block=p)
                for p in [None] + blocks for o in GRID_ORDERS]
    if lg is None:
        return [Candidate(o) for o in GRID_ORDERS]
    divisors = [r for r in range(1, lg) if lg % r == 0]
    out = []
    for br in [None] + divisors:
        for order in GRID_ORDERS:
            out.append(Candidate(order, br))
    return out


def vmem_working_set(geom: Geometry, *, rows: int, itemsize: int = 4) -> int:
    """Scoped-VMEM bytes one prefill grid step stages for a ``rows``-row
    block — the kernel's own lane-padded model
    (:func:`prefill_kernel.vmem_bytes`), at the workloads' dtype."""
    return vmem_bytes(rows, d=geom.d, ps=geom.page_size,
                      q_itemsize=itemsize, kv_itemsize=itemsize)


def feasible(cand: Candidate, *, op: str, lg: int, geom: Geometry,
             spec: TpuSpec = DEFAULT_TPU) -> tuple[bool, str]:
    """Static feasibility — infeasible tilings never run.  Rejects
    unknown grid orders, row tiling on decode (no row axis), non-divisor
    ``block_rows``, row blocks Mosaic would refuse (neither a multiple of
    the sublane tile nor the whole row axis), and tilings whose per-step
    working set overflows the kernel's VMEM limit."""
    if cand.grid_order not in GRID_ORDERS:
        return False, f"unknown grid_order {cand.grid_order!r}"
    if cand.pages_per_block is not None:
        if op != "decode":
            return False, f"{op} walks one page per step, not a page block"
        if cand.pages_per_block <= 0:
            return False, (f"pages_per_block={cand.pages_per_block} is "
                           f"not positive")
    if cand.block_rows is None:
        rows = default_block_rows(lg, d=geom.d, ps=geom.page_size,
                                  q_itemsize=4, kv_itemsize=4,
                                  limit=spec.vmem_bytes // 2)
    else:
        if op == "decode":
            return False, "decode has no query-row axis to tile"
        if cand.block_rows <= 0 or lg % cand.block_rows:
            return False, (f"block_rows={cand.block_rows} does not divide "
                           f"the fused row count Lq*G={lg}")
        if cand.block_rows % spec.sublane_tile and cand.block_rows != lg:
            return False, (f"block_rows={cand.block_rows} is not a multiple "
                           f"of the {spec.sublane_tile}-row sublane tile")
        rows = cand.block_rows
    # the same half-of-scoped-VMEM budget the kernel's default rule keeps
    # (the compiler may place operands in the other half)
    ws, limit = vmem_working_set(geom, rows=rows), spec.vmem_bytes // 2
    if ws > limit:
        return False, f"VMEM working set {ws} B exceeds {limit} B"
    return True, "ok"


def _block_rows(wl: Workload, block_rows: int | None) -> int:
    """The row block a candidate launches with (None: the default)."""
    if block_rows is not None:
        return block_rows
    item = jnp.dtype(wl.q.dtype).itemsize
    return default_block_rows(wl.lg, d=wl.geom.d, ps=wl.geom.page_size,
                              q_itemsize=item, kv_itemsize=item)


def _page_fetches(wl: Workload, block_rows: int | None) -> int:
    """Physical K/V page fetches across the whole grid for a candidate
    row tiling: each row block re-walks the page list, but only up to
    its own causal top (the dead-page skip redirects the rest)."""
    p_max = int(wl.table.shape[1])
    ps = wl.geom.page_size
    ln = np.asarray(wl.lengths, np.int64)
    if wl.op == "decode":
        end = np.clip(ln, 0, p_max * ps)
        return int(np.sum((end + ps - 1) // ps))
    off = np.asarray(wl.q_offset, np.int64)
    br = _block_rows(wl, block_rows)
    g = wl.geom.g
    total = 0
    for r in range(-(-wl.lg // br)):
        top = off + (r * br + br - 1) // g        # deepest qpos in block
        end = np.clip(np.minimum(ln, top + 1), 0, p_max * ps)
        total += int(np.sum((end + ps - 1) // ps))
    return total


def candidate_traffic(wl: Workload, cand: Candidate) -> tuple:
    """Per-candidate ``(mem_bytes, flops, onchip_bytes)``: the base
    :func:`ops._traffic` estimate, with the K/V component re-derived
    from the candidate's actual page-fetch count (row blocks re-walk
    pages; the causal top-skip refunds pages above each block).  Bytes
    moved from HBM to the re-walk are debited from on-chip reuse."""
    from .ops import _traffic
    mem, flops, onchip = _traffic(wl.q, wl.k_pages, wl.table, wl.lengths,
                                  q_offset=wl.q_offset)
    extra = _page_fetches(wl, cand.block_rows) - _page_fetches(wl, None)
    if extra > 0:
        item = jnp.dtype(wl.k_pages.dtype).itemsize
        kv = extra * wl.geom.page_size * wl.geom.hkv * wl.geom.d * item * 2
        mem += kv
        onchip = max(0.0, onchip - kv)
    return mem, flops, onchip


def score(cand: Candidate, wl: Workload,
          spec: TpuSpec = DEFAULT_TPU) -> float:
    """Analytic time estimate (ns) for ranking: roofline max of memory
    and compute time — compute derated by sublane occupancy of the row
    block — plus a dispatch charge per grid step."""
    mem, flops, _onchip = candidate_traffic(wl, cand)
    rows = _block_rows(wl, cand.block_rows)
    sublane_eff = min(1.0, rows / spec.sublane_tile)
    mem_t = mem / spec.hbm_gbps
    comp_t = flops / (spec.peak_flops_per_ns * sublane_eff)
    b, p_max = int(wl.table.shape[0]), int(wl.table.shape[1])
    if wl.op == "decode":
        ppb = cand.pages_per_block or default_pages_per_block(
            wl.geom.page_size, p_max)
        steps = b * wl.geom.hkv * -(-p_max // min(ppb, p_max))
    else:
        steps = b * wl.geom.hkv * p_max * -(-wl.lg // rows)
    return max(mem_t, comp_t) + steps * DISPATCH_NS


def prune(wl: Workload, candidates: list[Candidate] | None = None, *,
          budget: int | None = None,
          spec: TpuSpec = DEFAULT_TPU) -> tuple[list, list]:
    """(survivors, pruned): feasible candidates ranked by analytic score
    and cut to ``budget``, with the default shape always surviving (it
    is the parity baseline and the ``default_wall_s`` reference) and
    always first.  ``pruned`` pairs each rejected candidate with its
    reason."""
    if candidates is None:
        candidates = enumerate_candidates(wl.op, wl.lg,
                                          int(wl.table.shape[1]))
    kept, pruned = [], []
    for c in candidates:
        ok, why = feasible(c, op=wl.op, lg=wl.lg, geom=wl.geom, spec=spec)
        if ok:
            kept.append((score(c, wl, spec), c))
        else:
            pruned.append((c, why))
    kept.sort(key=lambda t: t[0])
    survivors = [c for _, c in kept]
    default = Candidate()
    if budget is not None and budget > 0 and len(survivors) > budget:
        cut = survivors[:budget]
        if default in survivors and default not in cut:
            cut[-1] = default
        pruned.extend((c, "over candidate budget (analytic rank)")
                      for c in survivors if c not in cut)
        survivors = cut
    if default in survivors:
        survivors.remove(default)
        survivors.insert(0, default)
    return survivors, pruned


def benchmark(wl: Workload, candidates: list[Candidate], *, reps: int = 3,
              interpret: bool | None = None) -> tuple[list, list]:
    """Measure ``candidates`` (default shape first) through the kernel
    route and the telemetry timing hooks.  Returns ``(rows, dropped)``:
    one result row per surviving candidate (config, per-call wall,
    achieved GB/s, op/byte) and the parity-gate casualties — candidates
    whose output is not bit-identical to the default shape's on this
    backend never reach winner selection."""
    from ..decode_attn import decode_attn_policy
    from . import ops as _ops
    if not candidates or candidates[0] != Candidate():
        raise ValueError("candidates[0] must be the default Candidate() — "
                         "it is the parity and default_wall_s baseline")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tel = _ops.attn_telemetry()

    def call(c: Candidate):
        if wl.op == "decode":
            return _ops.paged_attn(wl.q, wl.k_pages, wl.v_pages, wl.table,
                                   wl.lengths, interpret=interpret,
                                   grid_order=c.grid_order,
                                   pages_per_block=c.pages_per_block)
        fn = (_ops.paged_verify_attn if wl.op == "verify"
              else _ops.paged_prefill_attn)
        return fn(wl.q, wl.k_pages, wl.v_pages, wl.table, wl.q_offset,
                  wl.lengths, grid_order=c.grid_order,
                  block_rows=c.block_rows)

    rows, dropped = [], []
    ref = None
    # use_tuned=False: the sweep must measure exactly the candidate it
    # was handed, never a cached winner resolved under its None kwargs
    with decode_attn_policy(mode="kernel", interpret=interpret,
                            use_tuned=False):
        for c in candidates:
            out = np.asarray(call(c))          # compile + warmup, untimed
            if ref is None:
                ref = out
            elif not np.array_equal(out, ref):
                dropped.append({"config": c.as_dict(),
                                "reason": "output not bit-exact vs the "
                                          "default shape on this backend"})
                continue
            saved_enabled, saved_stats = tel.enabled, tel.stats
            tel.stats = {}
            tel.enabled = True
            try:
                for _ in range(reps):
                    call(c)
                snap = tel.snapshot().get(f"{wl.op}.kernel", {})
            finally:
                tel.enabled, tel.stats = saved_enabled, saved_stats
            rows.append({"config": c.as_dict(),
                         "wall_s": snap.get("wall_s", 0.0) / max(reps, 1),
                         "achieved_gbps": snap.get("achieved_gbps", 0.0),
                         "op_byte": snap.get("op_byte", 0.0)})
    return rows, dropped


def autotune(ops=OPS, *, geom: Geometry, b: int = 2, lq: int = 8,
             pages: int = 16, budget: int | None = None, reps: int = 3,
             interpret: bool | None = None, spec: TpuSpec = DEFAULT_TPU,
             seed: int = 0) -> dict:
    """Full sweep for one geometry: enumerate → prune → benchmark →
    pick the winner per op.  The winner is the wall-time argmin over the
    measured set, which always contains the default shape — so
    ``winner_wall_s <= default_wall_s`` holds by construction, and the
    parity gate guarantees the winner's output is bit-exact vs the
    default."""
    backend = jax.default_backend()
    results = {}
    for op in ops:
        wl = make_workload(op, geom, b=b, lq=lq, pages=pages, seed=seed)
        cands, pruned = prune(wl, budget=budget, spec=spec)
        rows, dropped = benchmark(wl, cands, reps=reps, interpret=interpret)
        winner = min(rows, key=lambda r: r["wall_s"])
        results[op] = {
            "key": entry_key(backend, op, geom),
            "backend": backend, "op": op, "geometry": geom.key(),
            "candidates": rows,
            "pruned": [{"config": c.as_dict(), "reason": why}
                       for c, why in pruned],
            "parity_dropped": dropped,
            "winner": winner["config"],
            "winner_wall_s": winner["wall_s"],
            "default_wall_s": rows[0]["wall_s"],
            "achieved_gbps": winner["achieved_gbps"],
            "op_byte": winner["op_byte"]}
    return results


# --------------------------------------------------------------------------
# tuned-shape cache: persistence + policy-side loading
# --------------------------------------------------------------------------

def resolve_cache_path(path: str | None = None) -> str | None:
    """The cache file to read: ``REPRO_TUNED_SHAPES`` overrides
    everything (a path, or one of ``0/off/ignore/none``/empty to disable
    loading → None); otherwise the explicit ``path``; otherwise the
    committed default."""
    env = os.environ.get(ENV_VAR)
    if env is not None:
        if env.strip().lower() in _ENV_OFF:
            return None
        return env
    return path or DEFAULT_CACHE


_load_memo: dict = {}


def load_entries(path: str | None = None) -> dict:
    """The cache's ``entries`` dict, or ``{}`` when loading is disabled,
    the file is missing/corrupt, or the schema is unknown — a broken
    cache must degrade to defaults, never break serving.  Memoized by
    (path, mtime, size) so per-policy-construction loads are one stat."""
    p = resolve_cache_path(path)
    if p is None:
        return {}
    try:
        st = os.stat(p)
    except OSError:
        return {}
    key = (p, st.st_mtime_ns, st.st_size)
    if key in _load_memo:
        return _load_memo[key]
    entries: dict = {}
    try:
        with open(p) as f:
            data = json.load(f)
        if isinstance(data, dict) and data.get("schema") == SCHEMA \
                and isinstance(data.get("entries"), dict):
            entries = data["entries"]
    except (OSError, ValueError):
        entries = {}
    _load_memo.clear()
    _load_memo[key] = entries
    return entries


def save_entries(results: dict, path: str | None = None) -> str:
    """Merge ``autotune()`` results into the cache at ``path`` (default:
    the committed ``benchmarks/tuned_shapes.json``), atomically.
    Existing entries for other (backend, op, geometry) keys are kept; an
    unknown on-disk schema is discarded rather than half-merged."""
    path = path or DEFAULT_CACHE
    data: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    if not isinstance(data, dict) or data.get("schema") not in (None, SCHEMA):
        data = {}
    data["schema"] = SCHEMA
    entries = data.setdefault("entries", {})
    if not isinstance(entries, dict):
        entries = data["entries"] = {}
    for op, r in results.items():
        entries[r["key"]] = {
            "config": r["winner"], "op": op, "geometry": r["geometry"],
            "wall_s": round(r["winner_wall_s"], 6),
            "default_wall_s": round(r["default_wall_s"], 6),
            "achieved_gbps": round(r["achieved_gbps"], 4),
            "op_byte": round(r["op_byte"], 4)}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path

"""Serving throughput + KV memory accounting: seed per-token host loop vs
device-resident engine, dense vs paged KV cache, prefix cache on vs off.

The seed ``Batcher`` ran decode as a per-token Python loop — eager
dispatch, host argmax, a fresh padded batch per round, O(n^2) queue drain.
The engine replaces that with slot-based continuous batching over a jitted
``lax.scan`` (repro.serve.scheduler); the paged mode additionally replaces
the per-slot ``max_len`` KV stripes with a block pool (repro.serve.kvpool)
so admission is on free pages and retired slots return memory.  Every row
therefore reports KV utilization (live tokens / allocated token capacity)
next to tokens/sec — the dense layout's stranded-stripe waste is the
number the paged pool exists to fix.  ``--prefix-cache`` runs a
repeated-system-prompt workload through the shared-prefix radix cache
(repro.serve.prefixcache) and reports the token hit rate plus prefill
tokens computed vs skipped.

  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--paged]
                                                  [--prefix-cache]
                                                  [--arch A]

``--prefill-chunk N`` serves through chunked prefill (page-aligned chunks
interleaved with decode segments); the full mode's ``chunked_compare``
runs a long+short mixed workload both ways and asserts chunking bounds
the worst-case join stall (``max_join_s`` — the decode pause every live
slot suffers while a prompt joins) without losing tokens.

``--speculate K`` serves through self-speculative decoding (draft-k
n-gram lookup + one multi-token verify per step, bit-identical greedy
output); the full mode's ``spec_compare`` runs the repetitive-
continuation workload both ways **in the steady serving state** — the
timed drain reuses the warm batcher's compiled executables, because a
fresh Batcher re-jits its join/segment closures and a compile-dominated
measurement says nothing about serving throughput — and asserts the
speculative engine reaches >= 1.5x tokens/sec at a live acceptance rate.

``--optimistic`` serves through optimistic admission (prompt-only pages
at admit, growth on demand, page-level preemption with recompute-on-
resume under pool pressure); the smoke forces exhaustion through the
chaos injector (repro.serve.chaos) and gates ``preemptions > 0`` plus
``recomputed_ok``, while the full mode's ``preempt_compare`` runs
reservation vs optimistic at the same undersized pool and asserts the
optimistic engine holds strictly more live slots at strictly higher KV
utilization with bit-identical greedy tokens.

``--overload`` serves with the degradation controller on while the chaos
injector exhausts the pool and injects a deadline-stamped low-priority
queue burst: the smoke gates ``cancellations > 0``, ``shed_requests >
0``, recovery to HEALTHY and zero orphaned pages; the full mode's
``overload_compare`` (also standalone via ``--overload-compare``) runs a
deadline-carrying 3x-capacity burst controller-on vs controller-off and
asserts the controller wins on deadline attainment at bit-identical
completed tokens.

Every row now also reports the request-latency trajectory (TTFT p50/p95
and time-per-output-token p50/p95, measured at host sync points), the
queue-wait p50/p95, the speculative ``acceptance_rate`` (0 with
speculation off), the preemption counters (0 in reservation mode) and
the overload counters (cancellations, sheds, deadline attainment,
degradation time-in-state — all zero/HEALTHY with the controller off).

``--smoke`` is the CI sanity mode (~5 s): engine only, asserts a nonzero
throughput (with ``--paged``: the paged engine, plus 100% page
reclamation; with ``--prefix-cache``: additionally a nonzero prefix hit
rate on the shared-prompt workload; with ``--prefill-chunk``: that chunk
continuations actually ran).  The full mode asserts the engine beats the
seed loop >= 3x, that at equal KV memory the paged pool either admits
more concurrent requests than dense or matches dense throughput within
10% while reclaiming every retired slot's pages, and that the prefix
cache cuts prefill tokens computed by exactly its hit rate without
losing concurrency.

Every invocation also appends its rows to ``BENCH_serve.json`` at the
repo root — the machine-readable perf trajectory future PRs regress
against (tokens/sec, KV utilization, prefix hit rate, prefill tokens
computed vs skipped).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config              # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import param as pm              # noqa: E402
from repro.models.model_zoo import Model          # noqa: E402
from repro.serve.chaos import ChaosInjector       # noqa: E402
from repro.serve.engine import ServeConfig        # noqa: E402
from repro.serve.scheduler import Batcher         # noqa: E402


BENCH_JSON = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, "BENCH_serve.json"))


def write_bench_json(rows: dict, path: str = BENCH_JSON) -> None:
    """Merge ``rows`` into the machine-readable perf trajectory.  Keys are
    stable row names (e.g. ``smoke-paged+prefix``) so successive PRs
    overwrite their own mode's numbers and diffs stay meaningful; the
    backend is stamped per row, so rows retained from a run on different
    hardware keep their provenance."""
    data: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    data["schema"] = 1
    data.setdefault("rows", {}).update(
        {k: dict({m: (round(v, 4) if isinstance(v, float) else v)
                  for m, v in row.items()},
                 backend=jax.default_backend())
         for k, row in rows.items()})
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def full_bench_rows(r: dict, capacity: dict, prefix: dict,
                    chunked: dict | None = None,
                    spec: dict | None = None,
                    preempt: dict | None = None,
                    overload: dict | None = None) -> dict:
    """The full-mode trajectory rows, assembled once for both entry
    points (CLI main and the benchmarks.run table hook)."""
    rows = {
        "full-dense": {k: r[k] for k in
                       ("engine_tok_s", "seed_tok_s", "speedup",
                        "kv_util_mean", "peak_live_slots")},
        "full-capacity-paged": capacity["paged"],
        "full-capacity-dense": capacity["dense"],
        "full-prefix-on": prefix["cache-on"],
        "full-prefix-off": prefix["cache-off"],
    }
    if chunked is not None:
        rows["full-chunked-on"] = chunked["chunked"]
        rows["full-chunked-off"] = chunked["unchunked"]
    if spec is not None:
        rows["full-spec-on"] = spec["spec-on"]
        rows["full-spec-off"] = spec["spec-off"]
    if preempt is not None:
        rows["full-preempt-optimistic"] = preempt["optimistic"]
        rows["full-preempt-reserve"] = preempt["reserve"]
    if overload is not None:
        rows["full-overload-on"] = overload["controller-on"]
        rows["full-overload-off"] = overload["controller-off"]
    return rows


def make_requests(vocab: int, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [(rid, rng.integers(0, vocab,
                               size=int(rng.integers(4, 12))).tolist())
            for rid in range(n)]


def make_shared_requests(vocab: int, n: int, prefix_len: int, seed: int = 0):
    """Repeated-system-prompt workload: every request carries the same
    ``prefix_len``-token system prefix plus a short random tail — the
    traffic shape the prefix cache exists for."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, size=prefix_len).tolist()
    return [(rid, system + rng.integers(
        0, vocab, size=int(rng.integers(2, 8))).tolist())
        for rid in range(n)]


def make_repetitive_requests(vocab: int, n: int, prompt_len: int = 12,
                             seed: int = 0):
    """Repetitive-continuation workload: every request is the same
    constant-token prompt.  The reduced random-init model's greedy
    continuation locks into short cycles on this shape, which is exactly
    the high-acceptance regime self-speculative decoding targets — the
    n-gram drafter proposes the cycle and the verify accepts nearly all
    of it.  (Chaotic continuations still decode correctly, just at ~1
    token per verify step; this workload measures the win, the parity
    tests pin the correctness.)"""
    rng = np.random.default_rng(seed)
    tok = int(rng.integers(0, vocab))
    return [(rid, [tok] * prompt_len) for rid in range(n)]


def make_long_mixed_requests(vocab: int, n: int, long_len: int,
                             n_long: int = 2, seed: int = 0):
    """Head-of-line workload: a few ``long_len``-token prompts scattered
    among short ones — the traffic shape whose unchunked join stalls
    every live slot's decode for the whole long prefill."""
    rng = np.random.default_rng(seed)
    longs = set(rng.choice(n, size=min(n_long, n), replace=False).tolist())
    return [(rid, rng.integers(
        0, vocab, size=long_len if rid in longs
        else int(rng.integers(4, 12))).tolist()) for rid in range(n)]


def seed_batcher_run(model, params, cfg: ServeConfig, requests, max_new):
    """The seed Batcher.run loop, verbatim semantics: padded batch rounds,
    eager per-token decode with host-side argmax, list.pop(0) drain."""
    queue = [(rid, list(p)) for rid, p in requests]
    results = {}
    while queue:
        batch = [queue.pop(0) for _ in range(min(cfg.batch, len(queue)))]
        width = max(len(p) for _, p in batch)
        toks = jnp.zeros((cfg.batch, width), jnp.int32)
        for i, (_, p) in enumerate(batch):
            toks = toks.at[i, :len(p)].set(jnp.asarray(p, jnp.int32))
        logits, caches = model.prefill(
            params, {"tokens": toks}, cfg.max_len, dtype=cfg.dtype)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        outs = [[] for _ in batch]
        length = jnp.asarray(width, jnp.int32)
        for _ in range(max_new):
            for i in range(len(batch)):
                outs[i].append(int(tok[i, 0]))
            logits, caches = model.decode_step(
                params, tok, caches, length, dtype=cfg.dtype)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            length = length + 1
        for (rid, _), out in zip(batch, outs):
            results[rid] = out
    return results


def engine_run(model, params, cfg: ServeConfig, requests, max_new,
               chaos=None, telemetry=None):
    """Returns (results, batcher) — the batcher carries the KV-utilization
    samples and, in paged mode, the page pool.  ``telemetry`` is an
    optional :class:`repro.serve.telemetry.Tracer` the run records into
    (warmup runs pass none, so a trace holds only the measured drain)."""
    b = Batcher(model, params, cfg, chaos=chaos, telemetry=telemetry)
    for rid, p in requests:
        b.submit(rid, p)
    return b.run(max_new=max_new), b


def _lat_row(batcher) -> dict:
    """The request-latency keys every trajectory row carries: TTFT and
    time-per-output-token p50/p95, as observed at host sync points."""
    lat = batcher.latency_stats()
    return {k: lat[k] for k in ("ttft_p50_s", "ttft_p95_s",
                                "tpot_p50_s", "tpot_p95_s")}


def bench(arch: str = "qwen2-0.5b", *, batch: int = 4, requests: int = 12,
          max_new: int = 24, max_len: int = 96, sync_every: int = 8,
          smoke: bool = False, paged: bool = False, page_size: int = 16,
          total_pages: int | None = None, prefix_cache: bool = False,
          shared_prefix: int = 0, prefill_chunk: int | None = None,
          speculate_k: int | None = None,
          admission_mode: str = "reserve", chaos=None,
          trace_out: str | None = None, attr_out: str | None = None,
          ttft_slo: float | None = None, tpot_slo: float | None = None,
          overload: bool = False, overload_opts: dict | None = None,
          seed: int = 0) -> dict:
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    scfg = ServeConfig(max_len=max_len, batch=batch, sync_every=sync_every,
                       paged=paged, page_size=page_size,
                       total_pages=total_pages, prefix_cache=prefix_cache,
                       prefill_chunk=prefill_chunk, speculate_k=speculate_k,
                       admission_mode=admission_mode,
                       ttft_slo_s=ttft_slo, tpot_slo_s=tpot_slo,
                       overload=overload, **(overload_opts or {}))
    if prefix_cache and not shared_prefix:
        shared_prefix = 2 * page_size      # two full shareable pages
    if speculate_k:
        # the workload speculation exists for: repetitive continuations.
        # Takes priority over the shared-prefix workload — a constant-
        # token prompt *is* a shared (and chunkable) prefix, so sized to
        # ``shared_prefix`` it still exercises --prefix-cache hits and
        # --prefill-chunk continuations while keeping the drafter's
        # high-acceptance regime (the smoke gates acceptance_rate > 0).
        reqs = make_repetitive_requests(
            cfg.vocab, requests, prompt_len=max(12, shared_prefix),
            seed=seed)
    elif shared_prefix:
        reqs = make_shared_requests(cfg.vocab, requests, shared_prefix,
                                    seed)
    else:
        reqs = make_requests(cfg.vocab, requests, seed)

    # engine: one warmup drain compiles the join/segment executables; the
    # timed drain is the steady serving state (same shapes, zero retraces).
    # Smoke mode skips the warmup — it only sanity-checks liveness.
    if not smoke:
        engine_run(model, params, scfg, reqs, max_new)
    tracer = None
    if trace_out:
        from repro.serve.telemetry import Tracer
        tracer = Tracer()
    t0 = time.perf_counter()
    got, batcher = engine_run(model, params, scfg, reqs, max_new,
                              chaos=chaos, telemetry=tracer)
    dt_engine = time.perf_counter() - t0
    if tracer is not None:
        tracer.to_perfetto(trace_out)
        print(f"[serve_bench] wrote Perfetto trace -> {trace_out} "
              f"({len(tracer.events)} events)")
    toks = sum(len(v) for v in got.values())
    util = batcher.kv_utilization()
    pstats = batcher.prefix_stats()
    jstats = batcher.join_stats()
    sstats = batcher.spec_stats()
    kstats = batcher.preempt_stats()
    lat = batcher.latency_stats()
    slo = batcher.slo_stats()
    out = {"arch": arch, "tokens": toks, "paged": paged,
           "prefix_cache": prefix_cache,
           "engine_tok_s": toks / dt_engine, "engine_s": dt_engine,
           "kv_util_mean": util["mean_util"],
           "kv_util_peak": util["peak_util"],
           "peak_live_slots": util["peak_live_slots"],
           "prefix_hit_rate": pstats["hit_rate"],
           "prefill_computed": pstats["prefill_computed"],
           "prefill_skipped": pstats["prefill_skipped"],
           "chunk_joins": jstats["chunk_joins"],
           "max_join_s": jstats["max_join_s"],
           "acceptance_rate": sstats["acceptance_rate"],
           "tokens_per_step": sstats["tokens_per_step"],
           "preemptions": kstats["preemptions"],
           "recomputed_ok": bool(kstats["recomputed_ok"]),
           "preempted_token_recompute": kstats["recompute_tokens"],
           "queue_wait_p50_s": lat["queue_wait_p50_s"],
           "queue_wait_p95_s": lat["queue_wait_p95_s"],
           "ttft_p50_s": lat["ttft_p50_s"], "ttft_p95_s": lat["ttft_p95_s"],
           "tpot_p50_s": lat["tpot_p50_s"], "tpot_p95_s": lat["tpot_p95_s"],
           "slo_enabled": slo["enabled"],
           "slo_attainment": slo["slo_attainment"]}
    # overload-protection trajectory: cancellation/shed tallies, deadline
    # attainment, watchdog trips and the degradation ladder's time-in-
    # state — all-zero/HEALTHY when the controller is off, so every row
    # is comparable across modes
    ostats = batcher.overload_stats()
    tis = ostats["controller"]["time_in_state"]
    out.update({
        "cancellations": ostats["cancellations"],
        "shed_requests": ostats["shed_requests"],
        "deadline_attainment": ostats["deadline_attainment"],
        "watchdog_trips": ostats["watchdog_trips"],
        "recovered_to_healthy":
            bool(ostats["controller"]["recovered_to_healthy"]),
        "overload_state": ostats["controller"]["state"],
        "time_healthy_s": tis["HEALTHY"],
        "time_degraded_s": tis["DEGRADED"],
        "time_shedding_s": tis["SHEDDING"]})
    if tracer is not None:
        # bottleneck attribution over the measured drain's trace: the
        # wave-level dominant components ride on the row; the full
        # per-request decomposition goes to --attr-out when asked for
        from repro.serve.attribution import attribution_report
        rep = attribution_report(tracer)
        out["dominant_ttft_component"] = rep["dominant_ttft_component"]
        out["dominant_tpot_component"] = rep["dominant_tpot_component"]
        if attr_out:
            with open(attr_out, "w") as f:
                json.dump(rep, f, indent=1)
            print(f"[serve_bench] wrote attribution report -> {attr_out} "
                  f"({rep['requests']} requests)")
    if paged:
        # a drained pool holds no mapped pages: everything is back on the
        # free list except prefix pages parked evictable-cached (zero
        # reserved cost — reclaimed on pressure) and pages a preempted-
        # then-retired slot left parked dead (allocatable capacity)
        out["pages_reclaimed"] = (
            batcher.pool.free_pages + batcher.pool.cached_pages
            + batcher.pool.preempted_pages == batcher.pool.n_pages
            and int(batcher.pool.refcount.sum()) == 0)

    if not smoke:
        t0 = time.perf_counter()
        ref = seed_batcher_run(model, params, scfg, reqs, max_new)
        dt_seed = time.perf_counter() - t0
        seed_toks = sum(len(v) for v in ref.values())
        out.update({"seed_tok_s": seed_toks / dt_seed, "seed_s": dt_seed,
                    "speedup": (toks / dt_engine) / (seed_toks / dt_seed)})
    return out


def capacity_compare(arch: str = "qwen2-0.5b", *, requests: int = 16,
                     max_new: int = 24, max_len: int = 96,
                     page_size: int = 16, seed: int = 0) -> dict:
    """Equal-KV-memory comparison: the dense slot table spends
    ``batch * max_len`` tokens of capacity on 4 slots; the paged pool
    spends the same tokens on pages and admits into 8 slots, so short
    requests run 2x as concurrently.  Returns both engines' peak live
    slots, throughput and utilization."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    reqs = make_requests(cfg.vocab, requests, seed)
    dense_batch = 4
    kv_tokens = dense_batch * max_len                 # equal KV memory
    dense_cfg = ServeConfig(max_len=max_len, batch=dense_batch)
    paged_cfg = ServeConfig(max_len=max_len, batch=2 * dense_batch,
                            paged=True, page_size=page_size,
                            total_pages=kv_tokens // page_size)

    res = {}
    for name, scfg in (("dense", dense_cfg), ("paged", paged_cfg)):
        engine_run(model, params, scfg, reqs, max_new)      # warmup
        t0 = time.perf_counter()
        got, b = engine_run(model, params, scfg, reqs, max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in got.values())
        util = b.kv_utilization()
        res[name] = {"tok_s": toks / dt, "s": dt,
                     "kv_util_mean": util["mean_util"],
                     "peak_live_slots": util["peak_live_slots"],
                     **_lat_row(b)}
        if name == "paged":
            res[name]["pages_reclaimed"] = (b.pool.free_pages
                                            == b.pool.n_pages)
    return res


def prefix_compare(arch: str = "qwen2-0.5b", *, requests: int = 12,
                   max_new: int = 16, max_len: int = 96,
                   page_size: int = 8, prefix_len: int = 32,
                   seed: int = 0) -> dict:
    """Prefix cache on vs off at equal pool size on a repeated-system-
    prompt workload.  On a hit, admission needs free pages only for the
    suffix + budget — the shared prefix pages are already resident — so
    the same pool admits more concurrent requests, and the join prefills
    proportionally fewer tokens (computed drops by exactly the hit
    tokens)."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    reqs = make_shared_requests(cfg.vocab, requests, prefix_len, seed)
    # pool sized so cache-off fits ~2 whole requests but the shared-prefix
    # path fits several more (prefix pages counted once, not per request)
    pages_per_req = -(-(prefix_len + 8 + max_new) // page_size)
    pool_pages = 2 * pages_per_req + 2
    base = dict(max_len=max_len, batch=8, sync_every=8, paged=True,
                page_size=page_size, total_pages=pool_pages)

    res = {}
    for name, on in (("cache-off", False), ("cache-on", True)):
        scfg = ServeConfig(**base, prefix_cache=on)
        engine_run(model, params, scfg, reqs, max_new)      # warmup
        t0 = time.perf_counter()
        got, b = engine_run(model, params, scfg, reqs, max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in got.values())
        util = b.kv_utilization()
        p = b.prefix_stats()
        res[name] = {"tok_s": toks / dt, "s": dt,
                     "kv_util_mean": util["mean_util"],
                     "peak_live_slots": util["peak_live_slots"],
                     "prefix_hit_rate": p["hit_rate"],
                     "prefill_computed": p["prefill_computed"],
                     "prefill_skipped": p["prefill_skipped"],
                     **_lat_row(b)}
    return res


def chunked_compare(arch: str = "qwen2-0.5b", *, requests: int = 8,
                    max_new: int = 16, max_len: int | None = None,
                    page_size: int = 16, chunk: int = 32,
                    long_len: int = 120, seed: int = 0) -> dict:
    """Chunked vs unchunked prefill on a long+short mixed workload at
    equal config.  The number under test is ``max_join_s``: every refill
    join stalls all live slots' decode for its duration, so an unchunked
    120-token prompt makes one long pause while the chunked engine takes
    several short page-aligned bites interleaved with decode segments —
    bounded join latency at identical token output (greedy)."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    if max_len is None:
        # the long prompts must fit whatever --max-new the caller picked
        max_len = long_len + max_new + 2 * page_size
    reqs = make_long_mixed_requests(cfg.vocab, requests, long_len,
                                    seed=seed)
    base = dict(max_len=max_len, batch=4, sync_every=8, paged=True,
                page_size=page_size)

    res = {}
    for name, ch in (("unchunked", None), ("chunked", chunk)):
        scfg = ServeConfig(**base, prefill_chunk=ch)
        engine_run(model, params, scfg, reqs, max_new)      # warmup
        t0 = time.perf_counter()
        got, b = engine_run(model, params, scfg, reqs, max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in got.values())
        j = b.join_stats()
        res[name] = {"tok_s": toks / dt, "s": dt, "tokens": toks,
                     "joins": j["joins"], "chunk_joins": j["chunk_joins"],
                     "max_join_s": j["max_join_s"],
                     "mean_join_s": j["mean_join_s"],
                     **_lat_row(b),
                     "tokens_by_rid": {r: v for r, v in got.items()}}
    # greedy parity is part of the bench contract, not just the tests
    assert (res["chunked"]["tokens_by_rid"]
            == res["unchunked"]["tokens_by_rid"]), \
        "chunked prefill changed sampled tokens"
    for r in res.values():
        del r["tokens_by_rid"]
    return res


def spec_compare(arch: str = "qwen2-0.5b", *, requests: int = 8,
                 max_new: int = 32, max_len: int = 96, page_size: int = 16,
                 batch: int = 4, k: int = 4, seed: int = 0) -> dict:
    """Self-speculative decoding on vs off on the repetitive-continuation
    workload, measured in the **steady serving state**: each engine's
    batcher drains one warmup wave (compiling its join + verify/decode
    executables), then the timed wave re-submits the same requests into
    the *same* batcher — a fresh Batcher would re-jit its closures and
    time compilation, not serving.  The number under test is tokens/sec
    at bit-identical greedy output: the verify step costs more than a
    one-token decode step (Lq = k+1), so speculation only wins where the
    drafter's acceptance rate is high — which this workload's cyclic
    continuations provide (the chaotic-workload case is covered by the
    parity tests, not benched as a win)."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    reqs = make_repetitive_requests(cfg.vocab, requests, seed=seed)
    base = dict(max_len=max_len, batch=batch, sync_every=8, paged=True,
                page_size=page_size)
    wave2 = 10 ** 6      # rid offset of the timed wave

    res = {}
    for name, sk in (("spec-off", None), ("spec-on", k)):
        scfg = ServeConfig(**base, speculate_k=sk)
        b = Batcher(model, params, scfg)
        for rid, p in reqs:
            b.submit(rid, p)
        b.run(max_new=max_new)                     # warmup wave: compiles
        # restart the measurement state so the row's TTFT/TPOT
        # percentiles and acceptance_rate describe the steady-state
        # wave, not a blend with the compile-laden warmup
        b.reset_stats()
        for rid, p in reqs:
            b.submit(rid + wave2, p)
        t0 = time.perf_counter()
        b.run(max_new=max_new)                     # steady-state wave
        dt = time.perf_counter() - t0
        got = {r - wave2: v for r, v in b.results.items() if r >= wave2}
        toks = sum(len(v) for v in got.values())
        s = b.spec_stats()
        res[name] = {"tok_s": toks / dt, "s": dt, "tokens": toks,
                     "speculate_k": sk or 0,
                     "acceptance_rate": s["acceptance_rate"],
                     "tokens_per_step": s["tokens_per_step"],
                     **_lat_row(b),
                     "tokens_by_rid": got}
    # bit-exact greedy parity is the contract speculation rides on
    assert (res["spec-on"]["tokens_by_rid"]
            == res["spec-off"]["tokens_by_rid"]), \
        "speculative decoding changed sampled tokens"
    for r in res.values():
        del r["tokens_by_rid"]
    return res


def preempt_compare(arch: str = "qwen2-0.5b", *, requests: int = 9,
                    max_new: int = 14, max_len: int = 96,
                    page_size: int = 8, pool_pages: int = 10,
                    batch: int = 6, sync_every: int = 4,
                    seed: int = 1) -> dict:
    """Reservation vs optimistic admission at the same undersized pool.
    Reservation admits on the worst case (prompt + max_new + margin), so
    the tight pool serializes requests whose actual footprints would have
    fit together; optimistic admission takes prompt-only pages, grows
    slots on demand, and preempts the policy victim (lowest priority,
    most pages, least progress) when growth hits pool pressure —
    recompute-on-resume keeps greedy output bit-identical.  The numbers
    under test: optimistic must run strictly more concurrent slots at
    strictly higher mean KV utilization, with at least one preemption
    actually exercised and every preempted request recomputed to the
    same tokens."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    rng = np.random.default_rng(seed)
    reqs = [(rid, rng.integers(0, cfg.vocab,
                               size=int(rng.integers(8, 14))).tolist())
            for rid in range(requests)]
    base = dict(max_len=max_len, batch=batch, sync_every=sync_every,
                paged=True, page_size=page_size, total_pages=pool_pages)

    res = {}
    for name, mode in (("reserve", "reserve"), ("optimistic", "optimistic")):
        scfg = ServeConfig(**base, admission_mode=mode)
        engine_run(model, params, scfg, reqs, max_new)      # warmup
        t0 = time.perf_counter()
        got, b = engine_run(model, params, scfg, reqs, max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in got.values())
        util = b.kv_utilization()
        k = b.preempt_stats()
        lat = b.latency_stats()
        res[name] = {"tok_s": toks / dt, "s": dt, "tokens": toks,
                     "kv_util_mean": util["mean_util"],
                     "peak_live_slots": util["peak_live_slots"],
                     "preemptions": k["preemptions"],
                     "recompute_tokens": k["recompute_tokens"],
                     "recomputed_ok": bool(k["recomputed_ok"]),
                     "queue_wait_p50_s": lat["queue_wait_p50_s"],
                     "queue_wait_p95_s": lat["queue_wait_p95_s"],
                     **_lat_row(b),
                     "tokens_by_rid": {r: v for r, v in got.items()}}
    # recompute-on-resume keeps greedy decode bit-identical to the
    # never-preempted run — the contract optimism rides on
    assert (res["optimistic"]["tokens_by_rid"]
            == res["reserve"]["tokens_by_rid"]), \
        "preemption/resume changed sampled tokens"
    for r in res.values():
        del r["tokens_by_rid"]
    o, rsv = res["optimistic"], res["reserve"]
    assert o["preemptions"] > 0, \
        "undersized-pool workload triggered no preemptions"
    assert o["recomputed_ok"], "a preempted request never completed"
    assert o["peak_live_slots"] > rsv["peak_live_slots"], \
        "optimistic admission did not raise concurrency at equal pool"
    assert o["kv_util_mean"] > rsv["kv_util_mean"], \
        "optimistic admission did not raise KV utilization at equal pool"
    return res


def overload_compare(arch: str = "qwen2-0.5b", *, wave: int = 4,
                     burst_factor: int = 3, max_new: int = 12,
                     max_len: int = 96, page_size: int = 8,
                     pool_pages: int = 12, batch: int = 4,
                     sync_every: int = 4, seed: int = 3) -> dict:
    """Degradation controller on vs off under a deadline-carrying
    ``burst_factor``x-capacity queue burst at the same undersized pool.

    Calibration avoids wall-clock flakiness: an unloaded reference
    batcher (ample pool, no deadlines) first drains the wave alone in
    the steady state, and every measured request's deadline is 2x that
    unloaded drain — reachable for the protected wave, unreachable for
    a burst serialized behind ``burst_factor``x the capacity.  The
    controller-off engine admits everything optimistically and thrashes:
    burst requests are deadline-cancelled (scored misses) once expiry or
    the remaining-budget projection catches them.  The controller-on
    engine trips SHEDDING on pool pressure and answers the burst with
    retryable RETRY_AFTER rejections — *excluded* from attainment (a
    fast rejection is not a latency violation) — so the wave's deadlines
    survive.  Gates: controller-on beats controller-off on deadline
    attainment, both sides drain with zero orphaned pages
    (``KVPool.check`` + full partition accounting), and every request
    that *completes* is bit-identical to the unloaded reference run
    (degradation changes when and whether work runs, never its
    tokens)."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(seed)))
    rng = np.random.default_rng(seed)
    n = wave * (1 + burst_factor)
    # >= page_size-token prompts: admission maps 2+ pages per slot, so a
    # full slot table alone puts the pool well past degrade_pressure
    reqs = [(rid, rng.integers(0, cfg.vocab,
                               size=int(rng.integers(page_size + 2,
                                                     2 * page_size))
                               ).tolist()) for rid in range(n)]
    wave_reqs, burst_reqs = reqs[:wave], reqs[wave:]
    wave2 = 10 ** 6          # rid offset of each batcher's warmup wave

    # unloaded reference: ample pool, no deadlines — the parity oracle
    # (greedy tokens are schedule-independent) and the deadline
    # calibration, both measured on a *warm* batcher (a fresh one would
    # time jit compilation, not serving)
    ref_cfg = ServeConfig(max_len=max_len, batch=batch,
                          sync_every=sync_every, paged=True,
                          page_size=page_size)
    rb = Batcher(model, params, ref_cfg)
    for rid, p in reqs:
        rb.submit(rid + wave2, p)
    rb.run(max_new=max_new)                    # warmup: compiles
    rb.reset_stats()
    for rid, p in wave_reqs:
        rb.submit(rid, p)
    t0 = time.perf_counter()
    rb.run(max_new=max_new)
    t_wave = time.perf_counter() - t0          # unloaded wave drain
    for rid, p in burst_reqs:
        rb.submit(rid, p)
    ref_all = dict(rb.run(max_new=max_new))    # parity oracle, all rids
    deadline = 2.0 * t_wave

    base = dict(max_len=max_len, batch=batch, sync_every=sync_every,
                paged=True, page_size=page_size, total_pages=pool_pages,
                admission_mode="optimistic")
    res = {}
    for name, on in (("controller-off", False), ("controller-on", True)):
        scfg = ServeConfig(**base, overload=on,
                           overload_degrade_pressure=0.5,
                           overload_shed_pressure=0.65,
                           overload_up_rounds=1, overload_down_rounds=2)
        b = Batcher(model, params, scfg)
        for rid, p in reqs:                    # warmup at full load: the
            b.submit(rid + wave2, p)           # timed run replays warm
        b.run(max_new=max_new)                 # shapes, no compiles
        b.reset_stats()
        for rid, p in wave_reqs:
            b.submit(rid, p, priority=0, deadline_s=deadline)
        for rid, p in burst_reqs:
            b.submit(rid, p, priority=-1, deadline_s=deadline)
        t0 = time.perf_counter()
        got = {rid: out for rid, out in b.run(max_new=max_new).items()
               if rid < wave2}
        dt = time.perf_counter() - t0
        b.pool.check()                         # no orphans, exact refcounts
        assert (b.pool.free_pages + b.pool.cached_pages
                + b.pool.preempted_pages == b.pool.n_pages), \
            f"{name}: pages unaccounted for after drain"
        # every request that completed did so bit-identically to the
        # unloaded reference — overload protection never changes tokens
        bad = [rid for rid, out in got.items() if out != ref_all[rid]]
        assert not bad, f"{name}: tokens diverged for rids {bad}"
        o = b.overload_stats()
        res[name] = {"tok_s": sum(len(v) for v in got.values()) / dt,
                     "s": dt, "completed": len(got),
                     "deadline_attainment": o["deadline_attainment"],
                     "deadline_met": o["deadline_met"],
                     "deadline_total": o["deadline_total"],
                     "cancellations": o["cancellations"],
                     "shed_requests": o["shed_requests"],
                     "rejections": len(o["rejections"]),
                     "preemptions": b.preemptions,
                     "controller_state": o["controller"]["state"],
                     **_lat_row(b)}
    off, on_ = res["controller-off"], res["controller-on"]
    assert on_["deadline_attainment"] > off["deadline_attainment"], \
        (f"degradation controller did not improve deadline attainment: "
         f"on {on_['deadline_attainment']:.2f} vs "
         f"off {off['deadline_attainment']:.2f}")
    assert on_["shed_requests"] > 0, \
        "controller-on burst produced no RETRY_AFTER sheds"
    return res


def prefill_kernel_timing(arch: str = "qwen2-0.5b", *, b: int = 4,
                          lq: int = 32, pages: int = 64,
                          page_size: int = 16, reps: int = 3) -> dict:
    """Pallas flash-prefill kernel (interpret off-TPU) vs the XLA gather
    ref on one suffix-prefill shape — reported for trajectory only (the
    interpreter is expected to lose off-TPU; the kernel path is routed in
    on real backends)."""
    from repro.kernels.paged_attn import (paged_prefill_attn_pallas,
                                          paged_prefill_attn_ref)
    cfg = get_config(arch).reduced()
    hq, hkv = cfg.n_heads, cfg.kv_heads
    d = cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, lq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((pages, hkv, page_size, d)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((pages, hkv, page_size, d)),
                    jnp.float32)
    p_max = pages // b
    tbl = jnp.asarray(rng.permutation(pages)[:b * p_max]
                      .reshape(b, p_max).astype(np.int32))
    off = jnp.asarray(rng.integers(0, (p_max - 2) * page_size - lq,
                                   size=b).astype(np.int32))
    ln = off + lq

    def timed(fn):
        fn(q, k, v, tbl, off, ln).block_until_ready()    # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q, k, v, tbl, off, ln)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps

    return {"kernel_interpret_s": timed(paged_prefill_attn_pallas),
            "xla_ref_s": timed(jax.jit(paged_prefill_attn_ref)),
            "backend": jax.default_backend()}


def autotune_compare(arch: str = "qwen2-0.5b", *, ops=None, b: int = 2,
                     lq: int = 8, pages: int = 16, page_size: int = 8,
                     budget: int | None = 8, reps: int = 3, seed: int = 0,
                     tuned_out: str | None = None) -> dict:
    """Generalize ``prefill_kernel_timing`` across the whole paged_attn
    family: sweep every launch config the kernels accept per op (grid
    order; row-fold tiling on prefill/verify), analytically prune with
    the roofline traffic model, benchmark survivors through the kernel
    telemetry hooks, and report one ``autotune-<op>`` row per op with
    the per-candidate measurements attached.  Winners optionally persist
    to ``tuned_out`` in the tuned-shape cache schema so the row is also
    the provenance record for the committed cache."""
    from repro.kernels.paged_attn import autotune as at
    cfg = get_config(arch).reduced()
    geom = at.Geometry(hq=cfg.n_heads, hkv=cfg.kv_heads,
                       d=cfg.resolved_head_dim, page_size=page_size)
    res = at.autotune(tuple(ops or at.OPS), geom=geom, b=b, lq=lq,
                      pages=pages, budget=budget, reps=reps, seed=seed)
    rows: dict = {}
    for op, r in res.items():
        assert r["winner"] is not None, f"{op}: no winner selected"
        assert r["winner_wall_s"] <= r["default_wall_s"], \
            f"{op}: winner slower than the default it was measured against"
        assert r["achieved_gbps"] > 0, f"{op}: no timed telemetry recorded"
        rows[f"autotune-{op}"] = {
            "geometry": geom.key(),
            "op": op,
            "winner": r["winner"],
            "winner_wall_s": r["winner_wall_s"],
            "default_wall_s": r["default_wall_s"],
            "achieved_gbps": r["achieved_gbps"],
            "op_byte": r["op_byte"],
            "n_candidates": len(r["candidates"]),
            "n_pruned": len(r["pruned"]),
            "n_parity_dropped": len(r["parity_dropped"]),
            "candidates": [
                {"config": c["config"], "wall_s": round(c["wall_s"], 6),
                 "achieved_gbps": round(c["achieved_gbps"], 4)}
                for c in r["candidates"]],
        }
    if tuned_out:
        at.save_entries(res, tuned_out)
    return rows


def roofline_probe(arch: str = "qwen2-0.5b", *, b: int = 2, lq: int = 8,
                   pages: int = 16, page_size: int = 8) -> dict:
    """Eagerly drive decode / prefill / verify once through the kernel
    route so the attention telemetry holds *timed* calls: the jitted
    serving path records its traffic at trace time but never wall time
    (by design — no sync in the hot loop), so achieved GB/s would stay 0
    without an eager probe.  Returns the three ``op.kernel`` snapshot
    rows."""
    from repro.kernels.decode_attn import decode_attn_policy
    from repro.kernels.paged_attn import (attn_telemetry, paged_attn,
                                          paged_prefill_attn,
                                          paged_verify_attn)
    cfg = get_config(arch).reduced()
    hq, hkv = cfg.n_heads, cfg.kv_heads
    d = cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.standard_normal((pages, hkv, page_size, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, hkv, page_size, d)),
                     jnp.float32)
    p_max = pages // b
    tbl = jnp.asarray(rng.permutation(pages)[:b * p_max]
                      .reshape(b, p_max).astype(np.int32))
    off = jnp.asarray(rng.integers(page_size, (p_max - 1) * page_size - lq,
                                   size=b).astype(np.int32))
    ln = off + lq
    q1 = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    qk = jnp.asarray(rng.standard_normal((b, lq, hq, d)), jnp.float32)
    tel = attn_telemetry()
    was = tel.enabled
    tel.enable()
    with decode_attn_policy(mode="kernel", interpret=True):
        paged_attn(q1, kp, vp, tbl, ln, interpret=True)
        paged_prefill_attn(qk, kp, vp, tbl, off, ln)
        paged_verify_attn(qk, kp, vp, tbl, off, ln)
    snap = tel.snapshot()
    if not was:
        tel.disable()
    return {k: snap[k] for k in ("decode.kernel", "prefill.kernel",
                                 "verify.kernel") if k in snap}


def print_roofline() -> None:
    """Dump the live roofline/amenability accounting accumulated by the
    run so far: per-(op, route) traffic, op/byte and achieved GB/s, then
    the paper's amenability verdict over the measured op mix."""
    from repro.kernels.paged_attn import amenability_reports, attn_telemetry
    snap = attn_telemetry().snapshot()
    if not snap:
        return
    print("[roofline] analytic traffic per (op, route) — dead pages "
          "subtracted; GB/s over eagerly-timed calls only")
    for key, row in snap.items():
        print(f"  {key:<16} {row['calls']:>4} calls "
              f"({row['traced_calls']} traced), "
              f"{row['bytes'] / 1e6:8.2f} MB, "
              f"op/byte {row['op_byte']:6.2f}, "
              f"achieved {row['achieved_gbps']:.3f} GB/s")
    for _op, rep in sorted(amenability_reports().items()):
        print(rep.summary())


def run(table) -> None:
    """Hook for benchmarks.run: engine-vs-seed, dense-vs-paged and
    prefix-cache rows plus the paged-attention roofline; also refreshes
    BENCH_serve.json."""
    from repro.kernels.paged_attn import attn_telemetry
    tel = attn_telemetry()
    tel.reset()
    tel.enable()
    r = bench(requests=8, max_new=16, batch=4)
    table.add("serve seed per-token loop", r["seed_s"] * 1e9,
              f"{r['seed_tok_s']:.1f} tok/s")
    table.add("serve device-resident engine", r["engine_s"] * 1e9,
              f"{r['engine_tok_s']:.1f} tok/s ({r['speedup']:.1f}x, "
              f"KV util {r['kv_util_mean']:.0%})")
    c = capacity_compare(requests=12, max_new=16)
    table.add("serve paged KV pool (equal KV mem)",
              c["paged"]["s"] * 1e9,
              f"{c['paged']['tok_s']:.1f} tok/s, "
              f"{c['paged']['peak_live_slots']} live slots vs "
              f"{c['dense']['peak_live_slots']} dense, "
              f"KV util {c['paged']['kv_util_mean']:.0%} vs "
              f"{c['dense']['kv_util_mean']:.0%}")
    p = prefix_compare(requests=12, max_new=16)
    on, off = p["cache-on"], p["cache-off"]
    table.add("serve prefix cache (shared prompt)",
              on["s"] * 1e9,
              f"{on['tok_s']:.1f} tok/s, hit rate "
              f"{on['prefix_hit_rate']:.0%}, prefill "
              f"{on['prefill_computed']} vs {off['prefill_computed']} "
              f"tokens, {on['peak_live_slots']} vs "
              f"{off['peak_live_slots']} live slots")
    ch = chunked_compare(requests=8, max_new=16)
    con, coff = ch["chunked"], ch["unchunked"]
    table.add("serve chunked prefill (long prompts)",
              con["s"] * 1e9,
              f"{con['tok_s']:.1f} tok/s, max join stall "
              f"{con['max_join_s'] * 1e3:.0f}ms vs "
              f"{coff['max_join_s'] * 1e3:.0f}ms unchunked "
              f"({con['chunk_joins']} chunk joins)")
    sc = spec_compare(requests=8, max_new=32)
    son, soff = sc["spec-on"], sc["spec-off"]
    table.add("serve self-speculative decode (repetitive)",
              son["s"] * 1e9,
              f"{son['tok_s']:.1f} tok/s vs {soff['tok_s']:.1f} off "
              f"({son['tok_s'] / max(soff['tok_s'], 1e-9):.1f}x, accept "
              f"{son['acceptance_rate']:.0%}, "
              f"{son['tokens_per_step']:.1f} tok/step)")
    pr = preempt_compare()
    po, prs = pr["optimistic"], pr["reserve"]
    table.add("serve optimistic admission (undersized pool)",
              po["s"] * 1e9,
              f"{po['tok_s']:.1f} tok/s, {po['peak_live_slots']} vs "
              f"{prs['peak_live_slots']} live slots, KV util "
              f"{po['kv_util_mean']:.0%} vs {prs['kv_util_mean']:.0%} "
              f"({po['preemptions']} preemptions)")
    ov = overload_compare()
    oon, ooff = ov["controller-on"], ov["controller-off"]
    table.add("serve overload protection (3x burst + deadlines)",
              oon["s"] * 1e9,
              f"attainment {oon['deadline_attainment']:.0%} vs "
              f"{ooff['deadline_attainment']:.0%} uncontrolled "
              f"({oon['shed_requests']} shed, "
              f"{oon['cancellations']} cancelled)")
    for key, row in sorted(roofline_probe().items()):
        table.add(f"paged-attn roofline {key}", row["wall_s"] * 1e9,
                  f"{row['achieved_gbps']:.3f} GB/s achieved, "
                  f"op/byte {row['op_byte']:.2f}, "
                  f"{row['bytes'] / 1e6:.2f} MB moved")
    tel.disable()
    write_bench_json(full_bench_rows(r, c, p, ch, sc, pr, ov))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV-cache block pool")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix radix cache (needs --paged); runs "
                         "a repeated-system-prompt workload and reports "
                         "hit rate + prefill tokens computed vs skipped")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (needs --paged): admit prompts "
                         "in page-aligned chunks of this many tokens, "
                         "interleaved with decode segments")
    ap.add_argument("--speculate", type=int, default=None,
                    help="self-speculative decoding (needs --paged): "
                         "draft this many tokens per step from the "
                         "slot's own history and verify them in one "
                         "multi-token paged attention call (greedy, "
                         "bit-identical output); runs the repetitive-"
                         "continuation workload and reports the "
                         "acceptance rate")
    ap.add_argument("--optimistic", action="store_true",
                    help="optimistic admission + page-level preemption "
                         "(needs --paged): admit on prompt pages only, "
                         "grow on demand, preempt the policy victim on "
                         "pool pressure with recompute-on-resume; the "
                         "smoke forces pool exhaustion via the chaos "
                         "injector and gates preemptions > 0 + bit-safe "
                         "recompute, the full mode runs preempt_compare")
    ap.add_argument("--overload", action="store_true",
                    help="overload protection (needs --paged): serve "
                         "with the degradation controller on while the "
                         "chaos injector exhausts the pool and injects a "
                         "deadline-stamped low-priority queue burst; the "
                         "smoke gates cancellations > 0, shed > 0, "
                         "recovery to HEALTHY and zero orphaned pages")
    ap.add_argument("--overload-compare", action="store_true",
                    help="standalone controller-on vs controller-off "
                         "comparison under a deadline-carrying 3x-"
                         "capacity burst (the overload_compare gate: "
                         "controller-on must win on deadline attainment "
                         "at bit-identical completed tokens).  Runs "
                         "instead of the serve bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sanity: engine only, tiny sizes, ~5s")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the measured drain's request-lifecycle "
                         "trace and write it as Chrome/Perfetto "
                         "trace_event JSON (open at ui.perfetto.dev)")
    ap.add_argument("--attr-out", default=None, metavar="PATH",
                    help="write the per-request latency-attribution "
                         "report (TTFT/TPOT decomposed into queue / "
                         "prefill / recompute / stall components) as "
                         "JSON; needs --trace-out")
    ap.add_argument("--ttft-slo", type=float, default=None, metavar="S",
                    help="TTFT SLO in seconds: rows gain slo_attainment "
                         "(smokes default to a generous 60s so the gate "
                         "is deterministic)")
    ap.add_argument("--tpot-slo", type=float, default=None, metavar="S",
                    help="per-output-token SLO in seconds (see "
                         "--ttft-slo)")
    ap.add_argument("--autotune-compare", action="store_true",
                    help="standalone kernel-autotune sweep across decode/"
                         "prefill/verify: enumerate launch configs, prune "
                         "on the analytic roofline score, benchmark the "
                         "survivors and write per-candidate rows (config, "
                         "wall time, achieved GB/s, op/byte) into "
                         "BENCH_serve.json; with --smoke the sweep is "
                         "bounded for CI (<=4 measured candidates per op, "
                         "2 reps).  Runs instead of the serve bench")
    ap.add_argument("--tuned-out", default=None, metavar="PATH",
                    help="with --autotune-compare: also persist the "
                         "winners to this tuned-shape cache file")
    args = ap.parse_args()
    enable_compile_cache()
    if args.tuned_out and not args.autotune_compare:
        ap.error("--tuned-out requires --autotune-compare")
    if args.autotune_compare:
        rows = autotune_compare(
            args.arch,
            page_size=min(args.page_size, 8) if args.smoke
            else args.page_size,
            budget=4 if args.smoke else 8,
            reps=2 if args.smoke else 3,
            tuned_out=args.tuned_out)
        write_bench_json(rows)
        for name, row in sorted(rows.items()):
            print(f"[{name}] winner {row['winner']} "
                  f"{row['winner_wall_s'] * 1e3:.2f}ms "
                  f"(default {row['default_wall_s'] * 1e3:.2f}ms), "
                  f"{row['achieved_gbps']:.3f} GB/s over "
                  f"{row['n_candidates']} measured / "
                  f"{row['n_pruned']} pruned candidates")
        if args.tuned_out:
            print(f"[autotune] winners persisted to {args.tuned_out}")
        return
    if args.overload_compare:
        res = overload_compare(args.arch)
        write_bench_json({"full-overload-on": res["controller-on"],
                          "full-overload-off": res["controller-off"]})
        for name in ("controller-off", "controller-on"):
            row = res[name]
            print(f"[overload_compare] {name}: attainment "
                  f"{row['deadline_attainment']:.0%} "
                  f"({row['deadline_met']}/{row['deadline_total']}), "
                  f"{row['completed']} completed, "
                  f"{row['shed_requests']} shed, "
                  f"{row['cancellations']} cancelled, "
                  f"{row['preemptions']} preemptions")
        return
    if args.attr_out and not args.trace_out:
        ap.error("--attr-out requires --trace-out (attribution walks "
                 "the recorded trace)")
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged")
    if args.optimistic and not args.paged:
        ap.error("--optimistic requires --paged")
    if args.overload and not args.paged:
        ap.error("--overload requires --paged")
    if args.speculate is not None:
        if not args.paged:
            ap.error("--speculate requires --paged")
        if args.speculate < 1:
            ap.error("--speculate must be >= 1")
    if args.prefill_chunk is not None:
        if not args.paged:
            ap.error("--prefill-chunk requires --paged")
        if args.prefill_chunk <= 0:
            ap.error("--prefill-chunk must be positive")
        if args.prefill_chunk % args.page_size:
            ap.error(f"--prefill-chunk must be a multiple of --page-size "
                     f"({args.page_size})")
    if args.smoke:
        smoke_ps = min(args.page_size, 8)
        chunk = args.prefill_chunk
        if chunk is not None:
            # the smoke shrinks the page size; re-align the chunk to it
            chunk = max(smoke_ps, chunk - chunk % smoke_ps)
        chaos = None
        overload_opts = None
        if args.overload:
            # the overload drill: the injector drains the free list at
            # round 1 (pool pressure 1.0 before anything admits) and
            # injects a deadline-stamped low-priority 8-request burst at
            # the same round, so the controller — with single-round
            # hysteresis at smoke sizes — climbs to SHEDDING by round 2
            # and sheds the burst with RETRY_AFTER *before* the
            # projection sweep could deadline-cancel it (round 1 has no
            # latency samples yet, so projections abstain).  Pages come
            # back at round 5, pressure collapses, and the ladder must
            # walk back to HEALTHY — the recovery the smoke gates on.
            chaos = ChaosInjector(exhaust_at={1: 0}, release_at=(5,),
                                  burst_at={1: 8}, burst_deadline_s=5.0,
                                  check_invariants=True)
            overload_opts = dict(overload_degrade_pressure=0.5,
                                 overload_shed_pressure=0.8,
                                 overload_up_rounds=1,
                                 overload_down_rounds=1,
                                 # keep the 4-request wave: only the
                                 # synthetic burst is sheddable
                                 overload_queue_keep=4)
        elif args.optimistic:
            # forced pool exhaustion right after the first admissions
            # (mid-growth, while slots still need pages): the injector
            # raids the free list at round 2 and hands it back at round
            # 5, guaranteeing at least one preemption even at smoke
            # sizes; per-round pool/prefix invariant checks ride along
            chaos = ChaosInjector(exhaust_at={2: 0}, release_at=(5,),
                                  check_invariants=True)
        r = bench(args.arch, batch=2, requests=4,
                  # speculation needs enough output for the drafter's
                  # cycle lookup to engage (acceptance_rate is gated > 0);
                  # preemption needs enough decode rounds for growth
                  # demand to hit the chaos-starved pool
                  max_new=12 if args.speculate else
                          10 if args.optimistic or args.overload else 4,
                  # chunked prompts carry a 2*chunk shared prefix — scale
                  # the window so any valid chunk size fits; speculative
                  # requests need prompt + max_new + k to fit
                  max_len=2 * chunk + 32 if chunk else
                          48 if (args.speculate or args.optimistic
                                 or args.overload) else 32,
                  sync_every=4, smoke=True, paged=args.paged,
                  page_size=smoke_ps, prefix_cache=args.prefix_cache,
                  prefill_chunk=chunk, speculate_k=args.speculate,
                  # tight pool so slot growth actually contends while
                  # the chaos injector holds pages back
                  total_pages=(10 if args.optimistic or args.overload
                               else None),
                  admission_mode=("optimistic"
                                  if args.optimistic or args.overload
                                  else "reserve"),
                  chaos=chaos, trace_out=args.trace_out,
                  attr_out=args.attr_out,
                  overload=args.overload, overload_opts=overload_opts,
                  # generous default SLOs keep smoke attainment at a
                  # deterministic 1.0 across runners while still
                  # exercising the whole monitor path
                  ttft_slo=(args.ttft_slo if args.ttft_slo is not None
                            else 60.0),
                  tpot_slo=(args.tpot_slo if args.tpot_slo is not None
                            else 60.0),
                  # at the smoke's tiny default prompts a chunk never
                  # splits — make every prompt long enough to take 2+
                  # bites (the shared prefix also feeds --prefix-cache)
                  shared_prefix=2 * chunk if chunk else 0)
        assert r["engine_tok_s"] > 0, r
        if args.paged:
            assert r["pages_reclaimed"], "retired pages were not reclaimed"
        if args.optimistic:
            assert r["preemptions"] > 0, \
                "chaos-starved pool forced no preemptions"
            assert r["recomputed_ok"], \
                "a preempted request did not complete via recompute"
        if args.prefix_cache:
            assert r["prefix_hit_rate"] > 0, \
                "shared-prompt workload produced no prefix-cache hits"
            assert r["prefill_skipped"] > 0, r
        if chunk:
            assert r["chunk_joins"] > 0, \
                "chunked smoke ran no chunk continuations"
        if args.speculate:
            assert r["acceptance_rate"] > 0, \
                "speculative smoke accepted no drafts on the " \
                "repetitive-continuation workload"
        if args.overload:
            assert r["cancellations"] > 0, \
                "overload smoke cancelled nothing"
            assert r["shed_requests"] > 0, \
                "SHEDDING never shed the chaos burst"
            assert r["recovered_to_healthy"], \
                "controller never walked back to HEALTHY after the burst"
        mode = ("overload" if args.overload
                else "preempt" if args.optimistic
                else "spec" if args.speculate
                else "chunked" if chunk
                else "paged+prefix" if args.prefix_cache
                else "paged" if args.paged else "dense")
        write_bench_json({f"smoke-{mode}": {
            "tok_s": r["engine_tok_s"], "tokens": r["tokens"],
            "kv_util_mean": r["kv_util_mean"],
            "prefix_hit_rate": r["prefix_hit_rate"],
            "prefill_computed": r["prefill_computed"],
            "prefill_skipped": r["prefill_skipped"],
            "chunk_joins": r["chunk_joins"],
            "acceptance_rate": r["acceptance_rate"],
            "tokens_per_step": r["tokens_per_step"],
            "preemptions": r["preemptions"],
            "recomputed_ok": r["recomputed_ok"],
            "preempted_token_recompute": r["preempted_token_recompute"],
            "ttft_p50_s": r["ttft_p50_s"], "ttft_p95_s": r["ttft_p95_s"],
            "tpot_p50_s": r["tpot_p50_s"], "tpot_p95_s": r["tpot_p95_s"],
            "slo_attainment": r["slo_attainment"],
            "cancellations": r["cancellations"],
            "shed_requests": r["shed_requests"],
            "deadline_attainment": r["deadline_attainment"],
            "watchdog_trips": r["watchdog_trips"],
            "recovered_to_healthy": r["recovered_to_healthy"],
            "time_healthy_s": r["time_healthy_s"],
            "time_degraded_s": r["time_degraded_s"],
            "time_shedding_s": r["time_shedding_s"],
            "pages_reclaimed": bool(r.get("pages_reclaimed", False))}})
        dom = (f", dominant TTFT {r['dominant_ttft_component']}"
               if "dominant_ttft_component" in r else "")
        ovl = (f", shed {r['shed_requests']}, cancelled "
               f"{r['cancellations']}, deadline attainment "
               f"{r['deadline_attainment']:.0%}, recovered="
               f"{r['recovered_to_healthy']}" if args.overload else "")
        print(f"[serve_bench --smoke] {mode}: {r['tokens']} tokens, "
              f"{r['engine_tok_s']:.1f} tok/s, "
              f"KV util {r['kv_util_mean']:.0%}, "
              f"prefix hit rate {r['prefix_hit_rate']:.0%}, "
              f"acceptance {r['acceptance_rate']:.0%}, "
              f"preemptions {r['preemptions']}{ovl}, "
              f"SLO attainment {r['slo_attainment']:.0%}{dom} "
              f"on {jax.default_backend()}")
        return
    from repro.kernels.paged_attn import attn_telemetry
    attn_telemetry().enable()      # roofline accounting over the full run
    r = bench(args.arch, batch=args.batch, requests=args.requests,
              max_new=args.max_new, max_len=args.max_len,
              sync_every=args.sync_every, paged=args.paged,
              page_size=args.page_size, prefix_cache=args.prefix_cache,
              prefill_chunk=args.prefill_chunk,
              speculate_k=args.speculate, trace_out=args.trace_out,
              attr_out=args.attr_out, ttft_slo=args.ttft_slo,
              tpot_slo=args.tpot_slo)
    mode = ("spec" if args.speculate
            else "paged+prefix" if args.prefix_cache
            else "paged" if args.paged else "dense")
    print(f"[serve_bench] arch={r['arch']} mode={mode} "
          f"tokens={r['tokens']} backend={jax.default_backend()}")
    print(f"  seed per-token loop : {r['seed_tok_s']:8.1f} tok/s "
          f"({r['seed_s']:.2f}s)")
    print(f"  device-resident loop: {r['engine_tok_s']:8.1f} tok/s "
          f"({r['engine_s']:.2f}s)")
    print(f"  speedup             : {r['speedup']:.2f}x")
    print(f"  KV utilization      : mean {r['kv_util_mean']:.1%}, "
          f"peak {r['kv_util_peak']:.1%} "
          f"(live tokens / allocated capacity)")
    if r["slo_enabled"]:
        print(f"  SLO attainment      : {r['slo_attainment']:.1%} "
              f"(ttft<={args.ttft_slo}s, tpot<={args.tpot_slo}s)")
    if "dominant_ttft_component" in r:
        print(f"  dominant TTFT cost  : {r['dominant_ttft_component']}")
    assert r["speedup"] >= 3.0, \
        f"serving regressed: engine only {r['speedup']:.2f}x the seed loop"

    c = capacity_compare(args.arch, max_new=args.max_new,
                         max_len=args.max_len, page_size=args.page_size)
    d, p = c["dense"], c["paged"]
    print(f"[capacity @ equal KV memory] dense: {d['tok_s']:.1f} tok/s, "
          f"peak {d['peak_live_slots']} live slots, "
          f"KV util {d['kv_util_mean']:.1%}")
    print(f"                             paged: {p['tok_s']:.1f} tok/s, "
          f"peak {p['peak_live_slots']} live slots, "
          f"KV util {p['kv_util_mean']:.1%}, "
          f"reclaimed={p['pages_reclaimed']}")
    assert (p["peak_live_slots"] > d["peak_live_slots"]
            or (p["tok_s"] >= 0.9 * d["tok_s"] and p["pages_reclaimed"])), \
        "paged pool shows no capacity or throughput win over dense"

    pc = prefix_compare(args.arch, max_new=args.max_new,
                        max_len=args.max_len)
    on, off = pc["cache-on"], pc["cache-off"]
    total = off["prefill_computed"] + off["prefill_skipped"]
    print(f"[prefix cache @ equal pool]  off: {off['tok_s']:.1f} tok/s, "
          f"prefill {off['prefill_computed']} tokens, "
          f"peak {off['peak_live_slots']} live slots")
    print(f"                              on: {on['tok_s']:.1f} tok/s, "
          f"prefill {on['prefill_computed']} tokens "
          f"(hit rate {on['prefix_hit_rate']:.1%}), "
          f"peak {on['peak_live_slots']} live slots")
    assert on["prefill_skipped"] > 0, "shared-prompt workload never hit"
    # computed drops by exactly the hit tokens: same total prompt work
    assert on["prefill_computed"] + on["prefill_skipped"] == total, pc
    assert on["peak_live_slots"] >= off["peak_live_slots"], \
        "prefix sharing lost concurrency at equal pool size"

    ch = chunked_compare(args.arch, max_new=args.max_new)
    con, coff = ch["chunked"], ch["unchunked"]
    print(f"[chunked prefill @ long+short] off: {coff['tok_s']:.1f} tok/s, "
          f"max join stall {coff['max_join_s'] * 1e3:.0f}ms "
          f"({coff['joins']} joins)")
    print(f"                                on: {con['tok_s']:.1f} tok/s, "
          f"max join stall {con['max_join_s'] * 1e3:.0f}ms "
          f"({con['joins']} joins, {con['chunk_joins']} continuations)")
    assert con["chunk_joins"] > 0, "long prompts were never chunked"
    # each chunked join does strictly less work than the one long join,
    # but max-of-few-wall-clock-samples is noisy — gate the mean hard and
    # give the max a 25% scheduling-noise allowance
    assert con["mean_join_s"] < coff["mean_join_s"], \
        "chunked prefill did not shrink the mean join stall"
    assert con["max_join_s"] < 1.25 * coff["max_join_s"], \
        "chunked prefill did not bound the worst-case join stall"

    sc = spec_compare(args.arch, k=args.speculate or 4)
    son, soff = sc["spec-on"], sc["spec-off"]
    spec_x = son["tok_s"] / max(soff["tok_s"], 1e-9)
    print(f"[self-speculative @ repetitive] off: {soff['tok_s']:.1f} tok/s")
    print(f"                                 on: {son['tok_s']:.1f} tok/s "
          f"({spec_x:.2f}x, k={son['speculate_k']}, acceptance "
          f"{son['acceptance_rate']:.1%}, "
          f"{son['tokens_per_step']:.2f} tok/step)")
    assert son["acceptance_rate"] > 0, \
        "repetitive-continuation workload accepted no drafts"
    assert spec_x >= 1.5, \
        f"speculative decoding only {spec_x:.2f}x on the repetitive-" \
        "continuation workload (want >= 1.5x)"

    pr = preempt_compare(args.arch)
    po, prs = pr["optimistic"], pr["reserve"]
    print(f"[preempt @ undersized pool] reserve: {prs['tok_s']:.1f} tok/s, "
          f"peak {prs['peak_live_slots']} live slots, "
          f"KV util {prs['kv_util_mean']:.1%}")
    print(f"                         optimistic: {po['tok_s']:.1f} tok/s, "
          f"peak {po['peak_live_slots']} live slots, "
          f"KV util {po['kv_util_mean']:.1%} "
          f"({po['preemptions']} preemptions, "
          f"{po['recompute_tokens']} tokens recomputed)")

    ov = overload_compare(args.arch)
    oon, ooff = ov["controller-on"], ov["controller-off"]
    print(f"[overload @ 3x burst + deadlines] off: attainment "
          f"{ooff['deadline_attainment']:.0%} "
          f"({ooff['deadline_met']}/{ooff['deadline_total']}, "
          f"{ooff['cancellations']} cancelled)")
    print(f"                                   on: attainment "
          f"{oon['deadline_attainment']:.0%} "
          f"({oon['deadline_met']}/{oon['deadline_total']}, "
          f"{oon['shed_requests']} shed with RETRY_AFTER)")

    kt = prefill_kernel_timing(args.arch)
    print(f"[prefill kernel]  pallas(interpret={kt['backend'] != 'tpu'}): "
          f"{kt['kernel_interpret_s'] * 1e3:.1f}ms / call, xla ref: "
          f"{kt['xla_ref_s'] * 1e3:.1f}ms / call on {kt['backend']}")
    roofline_probe(args.arch)
    print_roofline()
    write_bench_json(full_bench_rows(r, c, pc, ch, sc, pr, ov))


if __name__ == "__main__":
    main()

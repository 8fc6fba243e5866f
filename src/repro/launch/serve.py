"""Serving driver: continuous batching through the device-resident decode
loop (slot table + fused ``lax.scan`` segments, see repro.serve.scheduler).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 6 --max-new 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config
from .compile_cache import enable_compile_cache
from ..models import param as pm
from ..models.model_zoo import Model
from ..serve.engine import ServeConfig
from ..serve.scheduler import Batcher


def run(arch: str, *, reduced: bool = True, requests: int = 4,
        max_new: int = 8, batch: int = 4, max_len: int = 64,
        seed: int = 0, sync_every: int = 8, temperature: float = 0.0,
        eos_id: int | None = None, attn_mode: str = "auto",
        paged: bool = False, page_size: int = 16,
        total_pages: int | None = None, prefix_cache: bool = False,
        shared_prefix: int = 0, admission: str = "fifo",
        prefill_chunk: int | None = None,
        prefill_round_tokens: int | None = None,
        speculate_k: int | None = None,
        speculate_ngram: int = 2, optimistic: bool = False,
        trace_out: str | None = None,
        ttft_slo: float | None = None,
        tpot_slo: float | None = None,
        overload: bool = False,
        deadline_s: float | None = None,
        timeout_s: float | None = None,
        watchdog_rounds: int = 100_000,
        prompt_lens: list[int] | None = None) -> dict:
    """Serve random prompts (tokens drawn from ``seed``) through the
    continuous batcher.  ``prompt_lens`` gives each request's prompt
    length and replaces ``requests`` and the default 4-11 token draw."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = pm.unwrap(jax.jit(model.init)(jax.random.key(seed)))
    scfg = ServeConfig(max_len=max_len, batch=batch, sync_every=sync_every,
                       temperature=temperature, attn_mode=attn_mode,
                       paged=paged, page_size=page_size,
                       total_pages=total_pages, prefix_cache=prefix_cache,
                       admission=admission, prefill_chunk=prefill_chunk,
                       prefill_round_tokens=prefill_round_tokens,
                       speculate_k=speculate_k,
                       speculate_ngram=speculate_ngram,
                       admission_mode="optimistic" if optimistic
                       else "reserve",
                       telemetry=bool(trace_out),
                       ttft_slo_s=ttft_slo, tpot_slo_s=tpot_slo,
                       overload=overload,
                       watchdog_rounds=watchdog_rounds)
    b = Batcher(model, params, scfg, eos_id=eos_id, seed=seed)
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab, size=shared_prefix).tolist()
    if prompt_lens is None:
        prompt_lens = [None] * requests
    prompts = {}
    for rid, plen in enumerate(prompt_lens):
        if plen is None:
            plen = int(rng.integers(4, 12))
        prompts[rid] = system + rng.integers(0, cfg.vocab,
                                             size=plen).tolist()
        b.submit(rid, prompts[rid], deadline_s=deadline_s,
                 timeout_s=timeout_s)
    t0 = time.perf_counter()
    results = b.run(max_new=max_new)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in results.values())
    util = b.kv_utilization()
    pstats = b.prefix_stats()
    mode = (f"paged pool {b.pool.n_pages}x{b.pool.page_size}" if paged
            else "dense")
    if prefill_chunk:
        j = b.join_stats()
        mode += (f" + chunked prefill ({prefill_chunk} tok/chunk, "
                 f"{j['chunk_joins']} continuations, max join stall "
                 f"{j['max_join_s'] * 1e3:.0f}ms)")
        if prefill_round_tokens:
            mode += (f" + round budget ({prefill_round_tokens} tok, "
                     f"{j['budget_deferrals']} deferrals)")
    if prefix_cache:
        mode += (f" + prefix cache (hit rate "
                 f"{pstats['hit_rate']:.0%}, "
                 f"{pstats['prefill_skipped']} prefill tokens skipped)")
    sstats = b.spec_stats()
    if speculate_k:
        mode += (f" + speculative k={speculate_k} (acceptance "
                 f"{sstats['acceptance_rate']:.0%}, "
                 f"{sstats['tokens_per_step']:.2f} tok/step)")
    kstats = b.preempt_stats()
    if optimistic:
        mode += (f" + optimistic admission ({kstats['preemptions']} "
                 f"preemptions, {kstats['recompute_tokens']} tokens "
                 "recomputed)")
    lat = b.latency_stats()
    slo = b.slo_stats()
    print(f"[serve] {len(results)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s on {jax.default_backend()}, {mode}, "
          f"KV util {util['mean_util']:.0%}, TTFT p50 "
          f"{lat['ttft_p50_s'] * 1e3:.0f}ms)")
    if slo["enabled"]:
        print(f"[serve] SLO attainment {slo['slo_attainment']:.0%} "
              f"(ttft<={ttft_slo}s, tpot<={tpot_slo}s; burn rate "
              f"ttft {slo['burn_rate_ttft']:.2f} / "
              f"tpot {slo['burn_rate_tpot']:.2f} over the last "
              f"{slo['window']} samples)")
    ostats = b.overload_stats()
    if overload or deadline_s is not None or timeout_s is not None \
            or ostats["cancellations"]:
        ctl = ostats["controller"]
        by = ", ".join(f"{r}={n}" for r, n
                       in ostats["cancelled_by_reason"].items() if n)
        print(f"[serve] overload: {ostats['cancellations']} cancelled "
              f"({by or 'none'}), {ostats['shed_requests']} shed, "
              f"deadline attainment {ostats['deadline_attainment']:.0%} "
              f"({ostats['deadline_met']}/{ostats['deadline_total']}), "
              f"controller {ctl['state']}, "
              f"watchdog trips {ostats['watchdog_trips']}")
    attribution = None
    if trace_out:
        from ..serve.attribution import attribution_report
        attribution = attribution_report(b.telemetry)
        if attribution["requests"]:
            dom = attribution["dominant_ttft_component"]
            share = attribution["ttft"][dom]["share"]
            print(f"[serve] dominant TTFT component: {dom} "
                  f"({share:.0%} of total TTFT across "
                  f"{attribution['requests']} requests)")
        b.telemetry.to_perfetto(trace_out)
        print(f"[serve] wrote Perfetto trace -> {trace_out} "
              f"({len(b.telemetry.events)} events; open at "
              "ui.perfetto.dev)")
    return {"results": results, "prompts": prompts, "seconds": dt,
            "join": b.join_stats(), "tok_per_s": toks / dt, "kv_util": util,
            "prefix": pstats, "spec": sstats, "latency": lat,
            "preempt": kstats, "slo": slo, "overload": ostats,
            "attribution": attribution}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--attn-mode", default="auto",
                    choices=("auto", "kernel", "xla"))
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block pool + per-slot page tables")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--total-pages", type=int, default=None,
                    help="pool size in pages (default: dense-equivalent)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix radix cache over the page pool "
                         "(needs --paged): requests matching a cached "
                         "page-aligned prompt prefix share its pages and "
                         "prefill only their suffix; retired prefix pages "
                         "stay resident (evictable, LRU) at zero reserved "
                         "capacity")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (exercises --prefix-cache)")
    ap.add_argument("--admission", default="fifo",
                    choices=("fifo", "skip-ahead"),
                    help="paged admission order: fifo blocks on the queue "
                         "head; skip-ahead admits the first queued request "
                         "whose pages fit (bounded lookahead, aged so a "
                         "blocked head cannot starve)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (needs --paged): prefill each "
                         "prompt's uncached suffix at most this many "
                         "tokens per join round (multiple of --page-size), "
                         "interleaving long-prompt admission with decode "
                         "segments to bound the join stall")
    ap.add_argument("--prefill-round-tokens", type=int, default=None,
                    help="decode-priority budget: cap the total prefill "
                         "tokens (chunks + admissions) one refill round "
                         "may take, deferring the rest to later rounds")
    ap.add_argument("--speculate", type=int, default=None,
                    help="self-speculative decoding (needs --paged, "
                         "greedy): draft this many tokens per step from "
                         "the slot's own history (n-gram lookup) and "
                         "verify them in one multi-token paged attention "
                         "call — bit-identical output, fewer steps on "
                         "repetitive continuations")
    ap.add_argument("--speculate-ngram", type=int, default=2,
                    help="history-match width of the draft lookup")
    ap.add_argument("--optimistic", action="store_true",
                    help="optimistic admission (needs --paged): admit on "
                         "the prompt's pages only and grow on demand, "
                         "preempting the lowest-priority / most-pages / "
                         "least-progress slot on pool pressure "
                         "(recompute-on-resume, bit-identical output)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the run's request-lifecycle trace and "
                         "write it as Chrome/Perfetto trace_event JSON "
                         "(open at ui.perfetto.dev); also prints the "
                         "dominant TTFT bottleneck component from the "
                         "latency-attribution report")
    ap.add_argument("--ttft-slo", type=float, default=None, metavar="S",
                    help="TTFT SLO in seconds: the run reports per-class "
                         "attainment and windowed burn rate")
    ap.add_argument("--tpot-slo", type=float, default=None, metavar="S",
                    help="per-output-token SLO in seconds (see "
                         "--ttft-slo)")
    ap.add_argument("--overload", action="store_true",
                    help="enable the SLO-burn/pool-pressure degradation "
                         "controller (HEALTHY -> DEGRADED -> SHEDDING "
                         "with hysteresis): sheds speculation, shrinks "
                         "prefill chunks, freezes optimistic growth, and "
                         "sheds lowest-priority queued work under "
                         "sustained overload")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="S",
                    help="stamp every request with this completion "
                         "deadline; expired or provably-unreachable "
                         "requests are cancelled and their pages "
                         "reclaimed")
    ap.add_argument("--timeout-s", type=float, default=None, metavar="S",
                    help="hard per-request wall-clock timeout (cancelled "
                         "with reason 'timeout' when exceeded)")
    ap.add_argument("--watchdog-rounds", type=int, default=100_000,
                    help="progress watchdog: rounds without any forward "
                         "progress before the scheduler dumps a flight "
                         "bundle and force-sheds the blocking request")
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, reduced=args.reduced, requests=args.requests,
        max_new=args.max_new, batch=args.batch, max_len=args.max_len,
        sync_every=args.sync_every, temperature=args.temperature,
        eos_id=args.eos_id, attn_mode=args.attn_mode, paged=args.paged,
        page_size=args.page_size, total_pages=args.total_pages,
        prefix_cache=args.prefix_cache, shared_prefix=args.shared_prefix,
        admission=args.admission, prefill_chunk=args.prefill_chunk,
        prefill_round_tokens=args.prefill_round_tokens,
        speculate_k=args.speculate, speculate_ngram=args.speculate_ngram,
        optimistic=args.optimistic, trace_out=args.trace_out,
        ttft_slo=args.ttft_slo, tpot_slo=args.tpot_slo,
        overload=args.overload, deadline_s=args.deadline_s,
        timeout_s=args.timeout_s, watchdog_rounds=args.watchdog_rounds)


if __name__ == "__main__":
    main()

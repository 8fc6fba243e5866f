"""One run of one cell: set-up, ramp, measured window, drain, then the
comparison with the reference.  ``bench/run.py`` is the command line."""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import check, spec
from .client import Client
from .traffic import RequestStream, length_range

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")


class NoAccelerator(SystemExit):
    """Raised (exit code 2) when JAX finds no TPU or too few chips."""


class _PhaseEnd(Exception):
    """Raised from the client hook to leave ``Batcher.run`` at a phase
    boundary (the top of a round, before any of its work)."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_devices(chips: int) -> dict:
    """The devices JAX found, as the result line reports them; exits
    non-zero unless they are TPUs, enough of them, and in the peak
    table."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoAccelerator(f"bench: needs a TPU, JAX found {d.platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    spec.peaks(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, however quickly it compiled."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Compiles:
    """Counts lowerings and backend compilations (``jax.monitoring``)."""

    def __init__(self):
        from jax._src import monitoring
        self.monitoring = monitoring
        self.n = collections.Counter()
        monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        self.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.n["lowerings"] += 1
        elif event.endswith("backend_compile_duration"):
            self.n["backend_compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


@dataclasses.dataclass
class Overrides:
    """Hooks the tests use to run the harness on the CPU at a small size
    (the command line never sets them)."""
    device: dict | None = None          # skip the look for a chip
    arch: object = None                 # ArchConfig in place of the file's,
                                        # checked against dims all the same
    serve: dict | None = None           # ServeConfig fields to replace
    # calibration (bench/calibrate.py): programs shared between the runs
    # of one process, and the control's reading beside the program's
    share: dict | None = None
    control: bool = False
    keep_trace: str | None = None       # write the plain-form trace here


def _bucket_sets(cfg, mix: dict, max_new: int) -> tuple[set, set]:
    """Join widths and decode page caps the cell's traffic can reach,
    by the program's own bucketing."""
    from repro.serve.scheduler import _pow2_bucket
    pmin, pmax = length_range(mix["prompt_tokens"])
    chunk = cfg.prefill_chunk or cfg.max_len
    lo = 1 if pmax > chunk else pmin
    widths = {_pow2_bucket(x, lo=8, hi=cfg.max_len)
              for x in range(lo, min(chunk, pmax) + 1)}
    ps = cfg.page_size
    pages = range(-(-(pmin + max_new) // ps), -(-(pmax + max_new) // ps) + 1)
    caps = {_pow2_bucket(x, lo=2, hi=cfg.max_pages) for x in pages}
    return widths, caps


def warm(b, widths, caps, max_new: int) -> None:
    """Compile and run once every join width and decode page cap, on an
    idle batcher (all rows done, every page-table row unallocated, so
    nothing is written), and the two row updates a cancellation makes."""
    import jax.numpy as jnp
    n = b.cfg.batch
    for w in sorted(widths):
        out = b._join(
            b.params, b.caches, b.tok, b.lengths, b.done, b.remaining,
            jnp.asarray(np.zeros((n,), bool)),
            jnp.asarray(np.zeros((n, w), np.int32)),
            jnp.asarray(np.ones((n,), np.int32)),
            jnp.asarray(np.full((n,), max_new, np.int32)), b.key,
            jnp.asarray(b.pool.table), jnp.asarray(np.zeros((n,), np.int32)),
            jnp.asarray(np.zeros((n,), bool)))
        (b.caches, b.tok, b.lengths, b.done, b.remaining, b.key, first) = out
        np.asarray(first)
    steps = max(1, b.cfg.sync_every)
    for cap in sorted(caps):
        loop = b._loop(steps, cap)
        (carry, emitted) = loop(b.params, b.tok, b.caches, b.lengths, b.done,
                                b.remaining, b.key,
                                jnp.asarray(b.pool.table[:, :cap]))
        (b.tok, b.caches, b.lengths, b.done, b.remaining, b.key) = carry
        np.asarray(emitted)
    b.done = b.done.at[0].set(True)
    b.remaining = b.remaining.at[0].set(0)


def _spans(b, client) -> None:
    """Host spans around the batcher's phases, in the profiler's trace,
    so that idle device time can be put down to what the host was doing.
    Wrapping changes nothing the batcher computes."""
    import jax

    def wrap(name, fn):
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner
    client.pump = wrap("bench.client", client.pump)
    b._refill = wrap("bench.refill", b._refill)
    b._collect = wrap("bench.collect", b._collect)
    b._join = wrap("bench.join_call", b._join)
    for key in list(b._loops):
        b._loops[key] = wrap("bench.decode_call", b._loops[key])


def _busy(b) -> bool:
    return bool(b.queue) or any(r is not None for r in b.slot_rid)


def drive(b, client: Client, until: float, max_new: int,
          finished=None) -> None:
    """Serve until ``until`` (or until ``finished()``): the client's hook
    submits and stops requests at every round; while the batcher is idle
    the harness sleeps until the next request is due.  The phase ends at
    the top of the first round that finds it over, whose time the client
    keeps as ``t_phase_end``."""
    import jax
    clock = client.clock

    def tick(now):
        if now >= until or (finished is not None and finished()):
            client.t_phase_end = now
            raise _PhaseEnd
    client.on_phase = tick
    try:
        while True:
            client.pump(b)
            if _busy(b):
                b.run(max_new)
                continue
            nd = client.next_due()
            wait = (until if nd is None else min(nd, until)) - clock()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(wait)
    except _PhaseEnd:
        pass


def _p95(xs) -> float | None:
    return float(np.percentile(xs, 95)) if len(xs) else None


def run(workload: str, seed: int, seconds: int, trace: bool, t_start: float,
        cell: spec.Cell | None = None, ov: Overrides | None = None) -> dict:
    ov = ov or Overrides()
    cell = cell or spec.cell(workload)
    device = ov.device or require_devices(cell.chips)
    import jax
    import jax.numpy as jnp
    if ov.device is None:
        enable_compile_cache()
    compiles = Compiles()
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeConfig
    from repro.serve.scheduler import ContinuousBatcher

    cfg, mix = cell.config, cell.traffic
    ref, fam = spec.reference_module(cfg), spec.family_module(cfg)
    m = ref.dims(cfg)
    arch = ov.arch or fam.arch_config(cfg)
    fam.check(arch, m)
    serve = dict(cfg["serve"], **(ov.serve or {}))
    scfg = ServeConfig(dtype=jnp.bfloat16, **serve)
    max_new = int(mix["max_new"])

    w = ref.make_weights(m, seed)
    jax.block_until_ready(w)
    stream = RequestStream(mix, seed, m["vocab"], scfg.batch)
    client = Client(stream, mix, scfg.batch, m, fam)
    b = ContinuousBatcher(build_model(arch), fam.to_program(w), scfg,
                          seed=seed, chaos=client)
    if ov.share:
        # the jitted join and decode loops take the weights as arguments,
        # so one process may serve several seeds with the same programs
        b._join, b._loops = ov.share["join"], dict(ov.share["loops"])
    widths, caps = _bucket_sets(scfg, mix, max_new)
    warm(b, widths, caps, max_new)
    if ov.share is not None and not ov.share:
        ov.share.update(join=b._join, loops=dict(b._loops))
    _spans(b, client)
    t_warm = time.perf_counter()
    say(f"warmed {len(widths)} join widths {sorted(widths)} and "
        f"{len(caps)} decode caps {sorted(caps)} in "
        f"{t_warm - t_start:.1f}s; compilations so far "
        f"{compiles.snapshot()}")

    # ramp to steady state (a closed backlog for a number of rounds, an
    # open loop for a time), then open the window
    client.start(time.perf_counter())
    if client.closed:
        n_ramp = int(mix["ramp_rounds"])
        drive(b, client, math.inf, max_new,
              finished=lambda: client.rounds >= n_ramp)
    else:
        drive(b, client, client.t0 + float(mix["ramp_s"]), max_new)
    b.reset_stats()
    t_open = time.perf_counter()
    client.t_open, client.t_close = t_open, t_open + seconds
    c_open = compiles.snapshot()
    q_open = len(b.queue)
    tracer = _Tracer(client, t_open, mix) if trace else None
    # the window closes at the top of the first round at or after
    # ``t_close``: the tokens that round's pump finds are in the window,
    # and so is the time up to it
    client.counting = True
    drive(b, client, client.t_close, max_new)
    client.counting = False
    t_shut = client.t_phase_end
    c_close = compiles.snapshot()
    queue_waits = list(b.metrics.samples("lat.queue_wait_s"))
    queued = {"open": q_open, "close": len(b.queue)}
    window = [r for r in client.recs.values()
              if not client.closed and t_open <= r.due < client.t_close]
    if window:
        drive(b, client, client.t_close + float(mix.get("drain_s", seconds)),
              max_new,
              finished=lambda: all(r.done_t is not None for r in window))
    t_end = time.perf_counter()
    if tracer is not None:
        tracer.close()
    client.stop_all(b)
    compiles.close()
    new = {k: c_close.get(k, 0) - c_open.get(k, 0) for k in c_close}
    say(f"compilations inside the window: {new}")
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)

    # end-to-end numbers, all from the client's clock
    if client.closed:
        attempted = sum(1 for r in client.recs.values()
                        if r.last_t is not None and r.last_t >= t_open
                        and (r.first_t or math.inf) < client.t_close)
        failed = 0
        tpot_recs = [r for r in client.recs.values() if r.done_t is not None
                     and t_open <= r.done_t <= t_shut]
    else:
        attempted, failed = len(window), sum(1 for r in window
                                             if r.done_t is None)
        tpot_recs = [r for r in window if r.done_t is not None]
    # a request still without its first token when the drain ends counts
    # with the wait it had at that point (its TTFT is at least that)
    ttfts = [(r.first_t or t_end) - r.due for r in window]
    tpots = [(r.last_t - r.first_t) / (r.seen - 1) for r in tpot_recs
             if r.seen > 1]
    e2e = {"setup_s": t_open - t_start,
           "output_tok_s": client.window_tokens / (t_shut - t_open),
           "ttft_p95_s": _p95(ttfts),
           "tpot_p95_ms": None if not tpots else 1e3 * _p95(tpots)}
    completed = sum(1 for r in client.recs.values()
                    if r.done_t is not None and t_open <= r.done_t <= t_shut)
    say(f"window: {t_shut - t_open:.3f}s, {attempted} requests attempted, "
        f"{failed} unfinished, {completed} completed in it, {len(ttfts)} "
        f"TTFT and {len(tpots)} TPOT samples; generator late p95 "
        f"{_p95(client.lateness)}; queue {queued}")

    # free the program's state before the reference runs
    finished = [(rid, r.prompt_len + r.out_len)
                for rid, r in sorted(client.recs.items())
                if r.done_t is not None]
    served = {rid: list(b.outputs[rid][:client.recs[rid].out_len])
              for rid, _ in finished}
    prompts = {rid: stream.get(rid).prompt for rid, _ in finished}
    traced = tracer.reduce(ov.keep_trace) if tracer is not None else None
    b.caches = b.params = None
    del b, w
    gc.collect()

    t_ref = time.perf_counter()
    picks = check.sample(finished, int(cfg["correct"]["sample_requests"]),
                         seed)
    w = ref.make_weights(m, seed)
    gap = max((float(check.served_gaps(ref, w, m, prompts[r], served[r])
                     .max()) for r in picks), default=math.inf)
    limit = float(cfg["correct"]["max_logit_gap"])
    say(f"reference over {len(picks)} requests "
        f"({sum(len(served[r]) for r in picks)} served tokens) in "
        f"{time.perf_counter() - t_ref:.1f}s")
    compared = {"max_logit_gap": {"value": gap, "limit": limit}}
    correct = bool(picks) and gap <= limit
    if ov.control:
        compared["control_max_logit_gap"] = {"value": max(
            (float(check.served_gaps(ref, w, m, prompts[r], served[r],
                                     control=True).max()) for r in picks),
            default=math.nan), "limit": limit}

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "device": dict(device, memory_peak_bytes=int(mem))}
    if trace:
        rec = {"ledger": traced["ledger"], "peaks": traced["peaks"],
               "queue_waits": queue_waits}
        metrics = {}
        for mt in cell.per_layer:
            v = spec.metric_module(mt["name"]).read(rec, traced["trace"])
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        result["metrics"] = metrics
        result["device"].update(busy_s=traced["trace"].busy_s,
                                window_s=traced["trace"].window_s)
        result["breakdown"] = traced["trace"].breakdown()
    else:
        result["metrics"] = {
            mt["name"]: {"value": e2e[mt["name"]], "unit": mt["unit"]}
            for mt in cell.end_to_end if e2e[mt["name"]] is not None}
    result["window_compilations"] = new
    result["generator_late_p95_s"] = _p95(client.lateness)
    result["queued"] = queued
    result["completed_per_s"] = completed / (t_shut - t_open)
    result["compared"] = compared
    return result


class _Tracer:
    """Takes the profiler trace of a steady stretch of the window: from
    the first round ``trace_offset_s`` after the window opens to the
    first round ``trace_s`` later, with the client adding up the needed
    work of exactly the rounds inside it."""

    def __init__(self, client: Client, t_open: float, mix: dict):
        import jax
        self.jax, self.client = jax, client
        self.start_at = t_open + float(mix.get("trace_offset_s", 2.0))
        self.length = float(mix.get("trace_s", 4.0))
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state, self.span = "before", None
        client.on_tick = self.tick

    def tick(self, now: float) -> None:
        jax = self.jax
        if self.state == "before" and now >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.traced")
            self.span.__enter__()
            self.client.ledger = collections.Counter()
            self.client.account = True
            self.t0, self.state = now, "on"
        elif self.state == "on" and now >= self.t0 + self.length:
            self.close()

    def close(self) -> None:
        if self.state != "on":
            return
        self.client.account = False
        self.span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self, keep: str | None = None) -> dict:
        from . import trace as tr
        import jax
        try:
            t = tr.load_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if keep:
            import gzip
            import json
            with gzip.open(keep, "wt") as f:
                json.dump(t, f)
        kind = jax.devices()[0].device_kind
        return {"trace": tr.Reduced(t), "ledger": self.client.ledger,
                "peaks": spec.peaks(kind) if jax.devices()[0].platform
                == "tpu" else None}

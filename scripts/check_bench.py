#!/usr/bin/env python
"""Gate the serving perf trajectory: fresh BENCH_serve.json vs baseline.

Every ``serve_bench`` invocation writes its rows to ``BENCH_serve.json``;
this script compares them against the committed
``benchmarks/baselines/serve_baseline.json`` and fails (exit 1) when the
trajectory regresses — so a PR that quietly halves serving throughput or
breaks page reclamation fails CI instead of landing.  This is the
measurement discipline PrIM-style benchmarking argues for: the numbers
are only meaningful if something checks them on every change.

Checks per baseline row (extra rows in the fresh file that the baseline
does not pin are ignored, so local experiments don't trip the gate —
but every row the baseline *does* pin must be present in the fresh
``BENCH_serve.json``: a missing row fails loudly with its name, because
a silently-skipped bench tier would otherwise pass on the intersection):

* ``tok_s``: fresh >= BENCH_TOL x baseline (default 0.5 — wall-clock
  throughput varies across runners; the gate catches collapses, not
  noise).  Skipped with a note when the backends differ (a CPU baseline
  says nothing about TPU throughput).
* ``prefix_hit_rate`` / ``prefill_skipped``: must stay nonzero wherever
  the baseline has them nonzero (the radix cache still hits).
* ``acceptance_rate``: nonzero wherever the baseline has it nonzero
  (the self-speculative drafter still gets drafts accepted on the
  repetitive workload — a dead drafter silently degrades to 1
  token/step at a higher per-step cost).
* ``pages_reclaimed``: must stay truthy wherever the baseline pins it
  (retired slots still return their pages).
* ``chunk_joins``: nonzero wherever the baseline has it nonzero (long
  prompts still get chunked).
* ``preemptions``: nonzero wherever the baseline has it nonzero (the
  forced-exhaustion smoke still actually exercises preemption — a
  silently idle preemption path would pass every other gate).
* ``recomputed_ok``: must stay truthy wherever the baseline pins it
  (every preempted request completed via recompute-on-resume).
* ``slo_attainment``: wherever the baseline pins one, the fresh row must
  carry a numeric attainment in [0, 1] (the SLO monitor is still
  observing; the smokes run generous SLOs so the value itself is a
  deterministic 1.0).
* ``cancellations`` / ``shed_requests``: nonzero wherever the baseline
  has them nonzero (the overload smoke still actually cancels and
  sheds — an overload controller that never fires would pass every
  other gate while protecting nothing).
* ``recovered_to_healthy``: must stay truthy wherever the baseline pins
  it (the degradation ladder descends again once the burst drains; a
  controller stuck in SHEDDING is a one-way ratchet, not protection).
* ``deadline_attainment``: wherever the baseline pins one, the fresh
  row must carry a numeric attainment in [0, 1] (deadline accounting is
  still wired through retire *and* cancel).
* ``kv_util_mean``: in (0, 1.5] — paged sharing can push utilization
  above 1.0, but not past every-slot-shares-everything sanity.
* autotune rows (baseline has ``winner_wall_s``): the fresh sweep must
  have selected a winner config no slower than the measured default
  (the default is always in the measured set, so winner <= default by
  construction — a violation means selection broke) with
  ``achieved_gbps > 0`` (the kernel timing hooks recorded real
  walltime, not traced-only accounting).

``--tuned PATH`` additionally gates the tuned-shape cache the tuning
tier produces: schema 1, non-empty, at least one entry per op, sane
configs.  ``--tuned-only`` runs just that gate (the CI tune-smoke step
has no fresh bench rows to diff).

Always prints a one-line-per-row delta table (ci.sh runs it last as the
bench summary); ``--out PATH`` additionally writes that table to a file
so CI can upload it as an artifact next to ``BENCH_serve.json``.

  python scripts/check_bench.py [--bench PATH] [--baseline PATH]
                                [--out PATH]
  BENCH_TOL=0.4 python scripts/check_bench.py     # looser throughput gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(ROOT, "BENCH_serve.json")
BASELINE = os.path.join(ROOT, "benchmarks", "baselines",
                        "serve_baseline.json")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f).get("rows", {})


def _fmt(v) -> str:
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def check(bench_path: str = BENCH, baseline_path: str = BASELINE,
          tol: float | None = None, out_path: str | None = None) -> int:
    """Returns the number of failed checks (0 == gate passes)."""
    if tol is None:
        tol = float(os.environ.get("BENCH_TOL", "0.5"))
    try:
        fresh = _load(bench_path)
    except (OSError, ValueError) as e:
        print(f"check_bench: cannot read {bench_path}: {e}")
        return 1
    try:
        base = _load(baseline_path)
    except (OSError, ValueError) as e:
        print(f"check_bench: cannot read {baseline_path}: {e}")
        return 1

    failures = []
    lines = []
    for name in sorted(base):
        brow = base[name]
        frow = fresh.get(name)
        if frow is None:
            # every baseline-pinned row must be regenerated by the run:
            # a missing row means a bench tier silently stopped running,
            # which is itself a regression — never pass on the
            # intersection of the two row sets
            failures.append(f"{name}: row missing from {bench_path} "
                            "(bench tier did not run?)")
            lines.append(f"  {name:<22} MISSING")
            continue
        row_fail = []
        notes = []
        b_tok, f_tok = brow.get("tok_s"), frow.get("tok_s")
        if b_tok:
            if brow.get("backend") != frow.get("backend"):
                notes.append(f"tok/s not compared "
                             f"({brow.get('backend')} baseline vs "
                             f"{frow.get('backend')} run)")
            elif f_tok is None or f_tok < tol * b_tok:
                row_fail.append(
                    f"tok_s {_fmt(f_tok)} < {tol:.2f} x baseline "
                    f"{_fmt(b_tok)}")
        for key in ("prefix_hit_rate", "prefill_skipped", "chunk_joins",
                    "acceptance_rate", "preemptions", "cancellations",
                    "shed_requests"):
            if brow.get(key) and not frow.get(key):
                row_fail.append(f"{key} dropped to zero "
                                f"(baseline {_fmt(brow[key])})")
        if brow.get("pages_reclaimed") and not frow.get("pages_reclaimed"):
            row_fail.append("pages_reclaimed is no longer true")
        if brow.get("recomputed_ok") and not frow.get("recomputed_ok"):
            row_fail.append("recomputed_ok is no longer true "
                            "(a preempted request lost tokens)")
        if brow.get("recovered_to_healthy") \
                and not frow.get("recovered_to_healthy"):
            row_fail.append("recovered_to_healthy is no longer true "
                            "(degradation controller stuck degraded)")
        if "deadline_attainment" in brow:
            # wherever the baseline pins a deadline attainment, the fresh
            # row must carry a sane one — missing means the deadline
            # accounting silently stopped
            da = frow.get("deadline_attainment")
            if not isinstance(da, (int, float)) or isinstance(da, bool) \
                    or not 0.0 <= da <= 1.0:
                row_fail.append(f"deadline_attainment {_fmt(da)} missing "
                                "or outside [0, 1]")
        if "slo_attainment" in brow:
            # wherever the baseline pins an attainment, the fresh row
            # must carry a sane one — a missing value means the SLO
            # monitor silently stopped observing
            sa = frow.get("slo_attainment")
            if not isinstance(sa, (int, float)) or isinstance(sa, bool) \
                    or not 0.0 <= sa <= 1.0:
                row_fail.append(f"slo_attainment {_fmt(sa)} missing or "
                                "outside [0, 1]")
        util = frow.get("kv_util_mean")
        if util is not None and not 0.0 < util <= 1.5:
            row_fail.append(f"kv_util_mean {_fmt(util)} outside (0, 1.5]")
        if "winner_wall_s" in brow:
            # autotune rows: the sweep must have selected a winner no
            # slower than the default it was measured against (the
            # default is always in the measured set, so a violation
            # means the argmin broke), and the telemetry timing hooks
            # must have recorded real walltime (achieved GB/s > 0 —
            # zero means the sweep fell back to traced-only accounting)
            ww, dw = frow.get("winner_wall_s"), frow.get("default_wall_s")
            if not (isinstance(ww, (int, float)) and
                    isinstance(dw, (int, float)) and
                    not isinstance(ww, bool) and not isinstance(dw, bool)):
                row_fail.append("winner_wall_s/default_wall_s missing "
                                "(autotune sweep did not run?)")
            elif ww > dw:
                row_fail.append(f"winner_wall_s {_fmt(ww)} > default "
                                f"{_fmt(dw)} (winner selection broke)")
            if not isinstance(frow.get("winner"), dict):
                row_fail.append("winner config missing from autotune row")
            ag = frow.get("achieved_gbps")
            if not isinstance(ag, (int, float)) or isinstance(ag, bool) \
                    or ag <= 0:
                row_fail.append(f"achieved_gbps {_fmt(ag)} not > 0 "
                                "(kernel timing hooks recorded nothing)")

        delta = ""
        if b_tok and f_tok and brow.get("backend") == frow.get("backend"):
            delta = f"tok/s {_fmt(f_tok)} vs {_fmt(b_tok)} " \
                    f"({(f_tok / b_tok - 1) * 100:+.0f}%)"
        elif notes:
            delta = notes[0]
        status = "FAIL: " + "; ".join(row_fail) if row_fail else "ok"
        lines.append(f"  {name:<22} {delta:<34} {status}")
        failures.extend(f"{name}: {f}" for f in row_fail)

    report = [f"[check_bench] {bench_path} vs {baseline_path} "
              f"(BENCH_TOL={tol:.2f})"]
    report.extend(lines)
    if failures:
        report.append(f"[check_bench] {len(failures)} regression(s):")
        report.extend(f"  - {f}" for f in failures)
    else:
        report.append("[check_bench] trajectory ok")
    print("\n".join(report))
    if out_path:
        # the per-row delta table as a CI artifact next to BENCH_serve.json
        with open(out_path, "w") as f:
            f.write("\n".join(report) + "\n")
    return len(failures)


def check_trace(trace_path: str) -> int:
    """Trace liveness gate: the Perfetto export of the smoke run must be
    loadable JSON with a non-empty ``traceEvents`` list of well-formed
    ``trace_event`` records, and every submitted request must retire —
    a trace that silently stopped recording (or a request that vanished
    mid-lifecycle) fails here instead of shipping as a dead artifact.
    Returns the number of failed checks (0 == gate passes)."""
    failures = []
    try:
        with open(trace_path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print(f"[check_bench] cannot read trace {trace_path}: {e}")
        return 1
    evs = data.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        failures.append("traceEvents missing or empty")
        evs = []
    submitted: set = set()
    retired: set = set()
    for i, e in enumerate(evs):
        if not isinstance(e, dict) or "ph" not in e or "pid" not in e:
            failures.append(f"event {i} is not a trace_event record "
                            f"(missing ph/pid): {e!r}")
            continue
        ph = e["ph"]
        if ph != "M" and not isinstance(e.get("ts"), (int, float)):
            failures.append(f"event {i} ({ph} {e.get('name')}) has no "
                            "numeric ts")
        if ph == "X" and not isinstance(e.get("dur"), (int, float)):
            failures.append(f"span {i} ({e.get('name')}) has no dur")
        if ph in ("b", "e") and "id" not in e:
            failures.append(f"async event {i} ({e.get('name')}) has "
                            "no id")
        if ph == "i":
            rid = (e.get("args") or {}).get("rid")
            if e.get("name") == "SUBMIT" and rid is not None:
                submitted.add(rid)
            elif e.get("name") in ("RETIRE", "CANCEL") and rid is not None:
                # CANCEL is terminal like RETIRE: a deadline-cancelled or
                # shed request left the system deliberately, it did not
                # vanish mid-lifecycle
                retired.add(rid)
    if evs and not submitted:
        failures.append("trace has no SUBMIT events (tracer not wired "
                        "into the smoke run?)")
    lost = submitted - retired
    if lost:
        failures.append("submitted rids never retired or cancelled: "
                        f"{sorted(lost)}")
    if failures:
        print(f"[check_bench] trace gate {trace_path}: "
              f"{len(failures)} failure(s):")
        for f_ in failures:
            print(f"  - {f_}")
    else:
        print(f"[check_bench] trace gate {trace_path}: ok "
              f"({len(evs)} events, {len(submitted)} requests "
              "submitted+retired)")
    return len(failures)


def check_tuned(tuned_path: str,
                ops: tuple = ("decode", "prefill", "verify")) -> int:
    """Tuned-shape cache gate: the file the CI tuning tier produces (and
    uploads as an artifact) must be a schema-1 cache with at least one
    entry per op, and every entry must carry a loadable config — a sweep
    that silently persisted nothing (or an op the sweep dropped) fails
    here instead of shipping an empty cache.  Returns the number of
    failed checks (0 == gate passes)."""
    failures = []
    try:
        with open(tuned_path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print(f"[check_bench] cannot read tuned cache {tuned_path}: {e}")
        return 1
    if data.get("schema") != 1:
        failures.append(f"schema {data.get('schema')!r} != 1")
    entries = data.get("entries")
    if not isinstance(entries, dict) or not entries:
        failures.append("entries missing or empty (sweep persisted "
                        "nothing)")
        entries = {}
    for op in ops:
        if not any(f"|{op}|" in k for k in entries):
            failures.append(f"no tuned entry for op {op!r}")
    for key, ent in sorted(entries.items()):
        cfg = ent.get("config") if isinstance(ent, dict) else None
        if not isinstance(cfg, dict):
            failures.append(f"{key}: no config dict")
            continue
        if cfg.get("grid_order") not in (None, "bh", "hb"):
            failures.append(f"{key}: bad grid_order "
                            f"{cfg.get('grid_order')!r}")
        br = cfg.get("block_rows")
        if br is not None and (not isinstance(br, int)
                               or isinstance(br, bool) or br <= 0):
            failures.append(f"{key}: bad block_rows {br!r}")
        ppb = cfg.get("pages_per_block")
        if ppb is not None and (not isinstance(ppb, int)
                                or isinstance(ppb, bool) or ppb <= 0):
            failures.append(f"{key}: bad pages_per_block {ppb!r}")
    if failures:
        print(f"[check_bench] tuned-cache gate {tuned_path}: "
              f"{len(failures)} failure(s):")
        for f_ in failures:
            print(f"  - {f_}")
    else:
        print(f"[check_bench] tuned-cache gate {tuned_path}: ok "
              f"({len(entries)} entries, all ops covered)")
    return len(failures)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=BENCH)
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--tol", type=float, default=None,
                    help="throughput tolerance factor (default env "
                         "BENCH_TOL or 0.5)")
    ap.add_argument("--out", default=None,
                    help="also write the per-row delta table to this "
                         "file (uploaded as a CI artifact)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="also gate a Perfetto trace: loadable, "
                         "non-empty, every submitted rid retired")
    ap.add_argument("--tuned", default=None, metavar="PATH",
                    help="also gate a tuned-shape cache: schema 1, "
                         "non-empty, >=1 entry per op, sane configs")
    ap.add_argument("--tuned-only", action="store_true",
                    help="run only the tuned-cache gate (needs --tuned); "
                         "used by the CI tune-smoke step, which has no "
                         "fresh bench rows to diff")
    args = ap.parse_args()
    if args.tuned_only:
        if not args.tuned:
            ap.error("--tuned-only requires --tuned")
        sys.exit(1 if check_tuned(args.tuned) else 0)
    fails = check(args.bench, args.baseline, args.tol, args.out)
    if args.trace:
        fails += check_trace(args.trace)
    if args.tuned:
        fails += check_tuned(args.tuned)
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()

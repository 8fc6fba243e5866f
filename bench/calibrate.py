"""Readings for setting a cell's limits and rate, on the chip:

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 10
    python3 -m bench.calibrate --workload <cell> --seeds 1 --seconds 10 \
        --rates 1.0 --sweep-seconds 51 --fraction 0.8

Each seed is one run of the cell, as ``bench.run`` makes it, that also
reports the control: the reference computed in float8 at each served
position of the same sampled requests.  The readings that the limit of
``max_logit_gap`` is set from are the program's widest gap on sound runs
and the control's.  With ``--rates`` the cell's open-loop rate is first
replaced by each rate in turn, for one run with no drain, which reports
the queue depth at the window's open and close and the requests
completed per second in the window.  The knee is the completion rate at
the lowest of those rates whose queue grew over the window: the most the
chip sustains just past its limit (under a deeper queue the joins fill
more rows and more completes, at waits that grow without end).  With
``--fraction`` the seeds then run at that fraction of the knee.  The
runs of one call share their compiled programs.  One JSON line per run
on standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--sweep-seconds", type=int, default=0)
    ap.add_argument("--fraction", type=float, default=0.0)
    ap.add_argument("--trace-out", default="",
                    help="one traced run per seed; its trace is written "
                         "to this path (gzipped JSON), seed appended")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench.harness import runner, spec
    base = spec.cell(args.workload)
    runner.require_devices(base.chips)
    runner.enable_compile_cache()
    share: dict = {}
    seeds = [int(x) for x in args.seeds.split(",")]

    def at_rate(rate, **mix):
        return dataclasses.replace(base, traffic=dict(
            base.traffic, arrivals=dict(base.traffic["arrivals"],
                                        rate_per_s=rate), **mix))

    def one(cell, seed, seconds, **kw):
        t0 = time.perf_counter()
        res = runner.run(args.workload, seed, seconds, bool(kw.get(
            "keep_trace")), t0, cell=cell, ov=runner.Overrides(
                share=share, **kw))
        print(json.dumps(dict(res, seed=seed, wall_s=time.perf_counter()
                              - t0, rate=cell.traffic["arrivals"].get(
                                  "rate_per_s"))), flush=True)
        return res

    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",") if r):
        res = one(at_rate(rate, drain_s=0), seeds[0],
                  args.sweep_seconds or args.seconds)
        if knee is None and res["queued"]["close"] > res["queued"]["open"]:
            knee = res["completed_per_s"]
    cell = base
    if args.fraction and knee is not None:
        cell = at_rate(round(args.fraction * knee, 3))
        print(f"knee {knee!r} requests/s; seeds run at "
              f"{cell.traffic['arrivals']['rate_per_s']}", file=sys.stderr)
    for seed in seeds:
        keep = f"{args.trace_out}.{seed}.json.gz" if args.trace_out \
            else None
        one(cell, seed, args.seconds, control=True, keep_trace=keep)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)

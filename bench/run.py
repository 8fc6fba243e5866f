"""Run one cell of the benchmark on the chip this process finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.  Otherwise the
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``), and the last lines of standard error are the
numbers compared for ``correct``, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from bench.harness import runner
    try:
        res = runner.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    except runner.NoAccelerator as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(res), flush=True)
    for name, c in res["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown, whose runtime messages would otherwise
    # follow the compared numbers on standard error
    os._exit(code)

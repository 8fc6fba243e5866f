"""The whole run of a cell, on the CPU at a small size (open loop, traced),
and the command line's refusal to run without a TPU or without the
program."""
import json
import os
import shutil
import subprocess
import sys
import time

from bench.harness import runner, spec
from bench.tests import small

CELL = "starcoder2-3b.code_completion"


def test_open_loop_traced_run_end_to_end():
    c, ov = small.cell(CELL)
    res = runner.run(CELL, 2**31 + 202, 3, True, time.perf_counter(),
                     cell=c, ov=ov)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    # a traced run reports per-layer metrics only; on the CPU there is no
    # device plane, so only the registry's queue wait has something to read
    assert set(res["metrics"]) <= {m["name"] for m in c.per_layer}
    assert "queue_wait_p95_s" in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in res["device"] and "busy_s" in res["device"]


def _bench(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "qwen2-0.5b.offline_long_output",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if lines:
        try:
            json.loads(lines[-1])
        except ValueError:
            return True
        return False
    return True


def test_exits_nonzero_without_a_tpu():
    proc = _bench(spec.ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(str(tmp_path))
    assert proc.returncode != 0
    assert _no_result(proc)

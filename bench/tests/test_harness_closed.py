"""The whole run of a cell, on the CPU at a small size (closed backlog)."""
import time

from bench.harness import runner
from bench.tests import small

CELL = "qwen2-0.5b.offline_long_output"


def test_closed_backlog_run_end_to_end():
    c, ov = small.cell(CELL)
    res = runner.run(CELL, 2**31 + 101, 3, False, time.perf_counter(),
                     cell=c, ov=ov)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["window_compilations"] == {"lowerings": 0,
                                          "backend_compiles": 0}
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == small.TEST_LIMIT

"""The whole run of a cell, on the CPU at a small size (closed backlog)."""
import gzip
import json
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


def test_traced_run_keeps_the_program_spans(tmp_path):
    c, ov = small.cell(CELL)
    ov.keep_trace = str(tmp_path / "trace.json.gz")
    res = runner.run(CELL, 2**31 + 102, 3, True, time.perf_counter(),
                     cell=c, ov=ov)
    assert res["correct"] is True
    with gzip.open(ov.keep_trace, "rt") as f:
        spans = json.load(f)["program_spans"]
    args = {}
    for name, _, dur, a in spans:
        assert name.startswith("serve.") and dur >= 0
        args.setdefault(name, []).append(a)
    assert {"serve.round", "serve.join", "serve.pages"} <= set(args)
    assert all(set(a) == {"rows_computed", "width", "tokens"}
               for a in args["serve.join"])
    assert all(set(a) == {"live_tokens", "mapped_tokens"}
               for a in args["serve.pages"] if a)
    # the readers of the program's spans need no device
    for name in ("join_token_use_share", "kv_page_use_share"):
        assert 0 < res["metrics"][name]["value"] <= 100

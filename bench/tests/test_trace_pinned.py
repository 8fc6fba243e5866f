"""The numbers the accepted per-layer metrics read on the committed chip
traces, pinned to the bit: a change to the trace reduction
(``bench/harness/trace.py``) that keeps more of the trace must leave
them exactly as they are.  One trace per cell, and a qwen2 stretch that
also holds the program's ``serve.*`` spans, which the span readers
read."""
import gzip
import json
import os

import pytest

from bench.harness import spec
from bench.harness.trace import Reduced

DATA = os.path.join(os.path.dirname(__file__), "data")
LEDGER = {"prefill_flops": 1e12, "decode_flops": 2e12,
          "prefill_attn_flops": 3e10, "prefill_attn_bytes": 4e9,
          "decode_attn_flops": 5e9, "decode_attn_bytes": 6e9}
PINNED = {"decode_mfu": 0.9440885449280972,
          "paged_decode_attn_roofline": 1.17696162577125,
          "device_idle_share": 0.12926823394466824,
          "serve_mfu": 0.26848429036009674}
# one join (one group of rows) and the decode segment after it, with 1 ms
# on either side, cut from a traced run of starcoder2-3b.code_completion
PINNED_CODE = {"prefill_mfu": 0.7092067028797032,
               "paged_prefill_attn_roofline": 0.8481940199444469,
               "decode_mfu": 0.8845576384206261,
               "paged_decode_attn_roofline": 0.9606047780695242,
               "device_idle_share": 1.212555245896385}
# one round (a join of one group, its pages, its decode segment) with
# 1 ms on either side, cut from a traced run of
# qwen2-0.5b.offline_long_output that kept the program's spans
PINNED_SPANS = {"decode_mfu": 0.9598788824058277,
                "paged_decode_attn_roofline": 1.2113643251729778,
                "device_idle_share": 2.228942544841228,
                "serve_mfu": 1.2214019373607348,
                "join_token_use_share": 25.5859375,
                "kv_page_use_share": 22.372293462266136}
RECORDED = {
    "qwen2_offline_trace.json.gz": (PINNED, (5.664666956, 5.671999049)),
    "starcoder2_code_trace.json.gz": (PINNED_CODE, (1.86347233, 1.886345309)),
    "qwen2_offline_spans_trace.json.gz": (PINNED_SPANS,
                                          (1.219008507, 1.246798939)),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_accepted_metrics_read_the_pinned_values(name):
    pinned, busy_window = RECORDED[name]
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        r = Reduced(json.load(f))
    rec = {"ledger": LEDGER, "peaks": spec.peaks("TPU v5 lite"),
           "queue_waits": []}
    got = {n: spec.metric_module(n).read(rec, r) for n in pinned}
    assert got == pinned
    assert (r.busy_s, r.window_s) == busy_window


def test_span_readers_read_the_spans_arguments():
    with gzip.open(os.path.join(DATA, "qwen2_offline_spans_trace.json.gz"),
                   "rt") as f:
        r = Reduced(json.load(f))
    (join,) = r.program_spans("serve.join")
    (pages,) = r.program_spans("serve.pages")
    assert join[3] == {"rows_computed": 4, "width": 256, "tokens": 262}
    assert pages[3] == {"live_tokens": 34056, "mapped_tokens": 152224}
    assert 100 * 262 / (4 * 256) == PINNED_SPANS["join_token_use_share"]
    assert 100 * 34056 / 152224 == PINNED_SPANS["kv_page_use_share"]

"""The numbers the accepted per-layer metrics read on the committed chip
trace, pinned to the bit: a change to the trace reduction
(``bench/harness/trace.py``) that keeps more of the trace must leave
them exactly as they are."""
import gzip
import json
import os

from bench.harness import spec
from bench.harness.trace import Reduced

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "qwen2_offline_trace.json.gz")
LEDGER = {"prefill_flops": 1e12, "decode_flops": 2e12,
          "prefill_attn_flops": 3e10, "prefill_attn_bytes": 4e9,
          "decode_attn_flops": 5e9, "decode_attn_bytes": 6e9}
PINNED = {"decode_mfu": 0.9440885449280972,
          "paged_decode_attn_roofline": 1.17696162577125,
          "device_idle_share": 0.12926823394466824,
          "serve_mfu": 0.26848429036009674}


def test_accepted_metrics_read_the_pinned_values():
    with gzip.open(RECORDED, "rt") as f:
        r = Reduced(json.load(f))
    rec = {"ledger": LEDGER, "peaks": spec.peaks("TPU v5 lite"),
           "queue_waits": []}
    got = {name: spec.metric_module(name).read(rec, r) for name in PINNED}
    assert got == PINNED
    assert (r.busy_s, r.window_s) == (5.664666956, 5.671999049)

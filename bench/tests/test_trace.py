"""The reduction from a profiler trace to busy time, idle share, kernel
and program time and labelled idle gaps: on a hand-made trace whose
answers are known, and on small traces recorded on the chip, one per cell."""
import gzip
import json
import os

import pytest

from bench.harness import spec
from bench.harness.trace import Reduced, union

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000


def hand_trace():
    # window 0-100 ms; ops 10-30 (kernel), 20-40 (nested fusion),
    # 60-70, 95-120 (clipped to 95-100)
    return {
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["%while.1 = (...) while(...)", 10 * MS, 30 * MS],
                    ["%paged_prefill_attn_kernel.3 = bf16[2] custom-call(",
                     10 * MS, 20 * MS],
                    ["%fusion.7 = bf16[4] fusion(", 20 * MS, 20 * MS],
                    ["%_paged_attn_jit.2 = bf16[2] custom-call(", 60 * MS,
                     10 * MS],
                    ["%copy.1 = bf16[2] copy(", 95 * MS, 25 * MS]],
            "modules": [["jit_join(123)", 10 * MS, 30 * MS],
                        ["jit_loop(456)", 60 * MS, 10 * MS],
                        ["jit_loop(456)", 95 * MS, 25 * MS]]}],
        "host_spans": [["bench.traced", 0, 100 * MS],
                       ["bench.refill", 0, 45 * MS],
                       ["bench.collect", 40 * MS, 15 * MS],
                       ["bench.client", 70 * MS, 30 * MS]]}


def test_union_merges_overlaps():
    assert union([(5, 8), (0, 2), (1, 3), (8, 9), (10, 10)]) == \
        [(0, 3), (5, 9)]


def test_hand_trace_numbers():
    r = Reduced(hand_trace())
    assert r.window_s == pytest.approx(0.1)
    # busy: 10-40, 60-70, 95-100
    assert r.busy_s == pytest.approx(0.045)
    assert r.op_s(r"^%paged_prefill_attn_kernel[.\d]* = .*custom-call") == \
        pytest.approx(0.020)
    assert r.module_s(r"^jit_loop\(") == pytest.approx(0.015)
    gaps = r.idle_gaps()
    # 0-10 in refill, 40-60 mid 50 in collect, 70-95 mid 82.5 in client
    assert gaps == [("bench.client", pytest.approx(0.025)),
                    ("bench.collect", pytest.approx(0.020)),
                    ("bench.refill", pytest.approx(0.010))]
    bd = r.breakdown(k=2)
    # a while loop holds other operations: it is busy time, not a top op
    assert bd["device_ops"] == [
        ["%paged_prefill_attn_kernel.3 custom-call bf16[2]",
         pytest.approx(0.020)],
        ["%fusion.7 fusion bf16[4]", pytest.approx(0.020)]]
    assert len(bd["idle_gaps"]) == 2


def test_metric_files_read_the_hand_trace():
    r = Reduced(hand_trace())
    pk = spec.peaks("TPU v5 lite")
    led = {"prefill_flops": 197e12 * 0.003, "decode_flops": 197e12 * 0.001,
           "prefill_attn_flops": 197e12 * 0.002, "prefill_attn_bytes": 1.0,
           "decode_attn_flops": 1.0, "decode_attn_bytes": 819e9 * 0.001}
    rec = {"ledger": led, "peaks": pk, "queue_waits": [1.0, 2.0, 3.0]}
    read = lambda n: spec.metric_module(n).read(rec, r)
    assert read("device_idle_share") == pytest.approx(55.0)
    assert read("prefill_mfu") == pytest.approx(10.0)       # 3 ms of 30
    assert read("decode_mfu") == pytest.approx(100 * 0.001 / 0.015)
    assert read("paged_prefill_attn_roofline") == pytest.approx(10.0)
    assert read("paged_decode_attn_roofline") == pytest.approx(10.0)
    assert read("serve_mfu") == pytest.approx(4.0)
    assert read("queue_wait_p95_s") == pytest.approx(2.9)


def test_program_spans_inside_the_stretch_label_idle_gaps():
    t = hand_trace()
    t["program_spans"] = [
        # a round that opened before the stretch: out of the list, but
        # still a label where nothing inner is open
        ["serve.round", -5 * MS, 50 * MS, {}],
        ["serve.join", 2 * MS, 38 * MS,
         {"rows_computed": 4, "width": 64, "tokens": 100}],
        ["serve.pages", 45 * MS, 10 * MS,
         {"live_tokens": 300, "mapped_tokens": 512}],
        ["serve.pages", 60 * MS, 2 * MS,
         {"live_tokens": 100, "mapped_tokens": 128}],
        # runs past the stretch's end
        ["serve.join", 96 * MS, 10 * MS,
         {"rows_computed": 8, "width": 8, "tokens": 8}]]
    r = Reduced(t)
    assert r.program_spans("serve.join") == [t["program_spans"][1]]
    assert r.program_spans("serve.pages") == t["program_spans"][2:4]
    assert r.program_spans("serve.round") == []
    # 0-10 mid 5: serve.join (from 2 ms) inside bench.refill (from 0);
    # 40-60 mid 50: serve.pages inside bench.collect; 70-95: bench.client
    assert r.idle_gaps() == [("bench.client", pytest.approx(0.025)),
                             ("serve.pages", pytest.approx(0.020)),
                             ("serve.join", pytest.approx(0.010))]
    # the numbers that do not read spans stay as they were
    plain = Reduced(hand_trace())
    assert (r.busy_s, r.window_s) == (plain.busy_s, plain.window_s)
    rec = {"ledger": {}, "peaks": spec.peaks("TPU v5 lite"),
           "queue_waits": []}
    read = lambda n: spec.metric_module(n).read(rec, r)
    assert read("join_token_use_share") == pytest.approx(100 * 100 / 256)
    assert read("kv_page_use_share") == pytest.approx(100 * 400 / 640)
    # a trace that kept no program spans gives none, and no reading
    assert plain.program_spans("serve.join") == []
    assert spec.metric_module("kv_page_use_share").read(rec, plain) is None


def test_readers_find_nothing_in_an_empty_trace():
    r = Reduced({"devices": [], "host_spans": [["bench.traced", 0, MS]]})
    led = dict.fromkeys(("prefill_flops", "decode_flops",
                         "prefill_attn_flops", "prefill_attn_bytes",
                         "decode_attn_flops", "decode_attn_bytes"), 5)
    rec = {"ledger": led, "peaks": spec.peaks("TPU v5 lite"),
           "queue_waits": []}
    for m in spec.benchmark()["per_layer"]:
        assert spec.metric_module(m["name"]).read(rec, r) is None


RECORDED = ["qwen2_offline_trace.json.gz", "starcoder2_code_trace.json.gz",
            "qwen2_offline_spans_trace.json.gz"]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_trace(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        t = json.load(f)
    r = Reduced(t)
    assert 0 < r.busy_s <= r.window_s
    # the kernels and programs the metric files name are in it
    dec = spec.metric_module("paged_decode_attn_roofline").OP
    pre = spec.metric_module("paged_prefill_attn_roofline").OP
    assert r.op_s(dec) > 0 and r.op_s(pre) > 0
    assert r.module_s(spec.metric_module("decode_mfu").PROGRAM) > 0
    assert r.module_s(spec.metric_module("prefill_mfu").PROGRAM) > 0
    # programs never overlap on one chip: their sum is within the union
    assert r.module_s(r".") <= r.busy_s * 1.0001
    gaps = r.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(r.window_s - r.busy_s)

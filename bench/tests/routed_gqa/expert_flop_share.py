"""Share of the needed operations that the routed experts take, from the
family's own ledger key (a fixture of the harness's tests)."""
LAYER = "experts"
UNIT = "%"
MOVES = "ttft_p95_s"


def read(record, trace):
    led = record["ledger"]
    total = led["prefill_flops"] + led["decode_flops"]
    return 100.0 * led["expert_flops"] / total if total else None

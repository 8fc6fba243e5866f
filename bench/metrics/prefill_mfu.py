"""Share of the chip's bf16 peak that the join programs reach on needed
work: the operations of the prompt tokens the traced rounds really
prefilled, at their absolute depth (plus the logits of each prompt's
last position), over the device time of the join programs
(``jit_join``) times the peak."""
LAYER = "model step (serve/engine.py join and decode loop)"
UNIT = "%"
MOVES = "ttft_p95_s"
PROGRAM = r"^jit_join\("


def read(record, trace):
    t = trace.module_s(PROGRAM)
    flops = record["ledger"]["prefill_flops"]
    if t <= 0 or not flops:
        return None
    return 100.0 * flops / (t * record["peaks"]["bf16_flops_per_s"])

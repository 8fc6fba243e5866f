"""Share of the chip's bf16 peak that the decode-loop programs reach on
needed work: the operations of the output tokens the traced rounds
committed, up to each request's drawn length, over the device time of
the decode-loop programs (``jit_loop``, ``jit_spec_loop``) times the
peak."""
LAYER = "model step (serve/engine.py join and decode loop)"
UNIT = "%"
MOVES = "tpot_p95_ms"
PROGRAM = r"^jit_(spec_)?loop\("


def read(record, trace):
    t = trace.module_s(PROGRAM)
    flops = record["ledger"]["decode_flops"]
    if t <= 0 or not flops:
        return None
    return 100.0 * flops / (t * record["peaks"]["bf16_flops_per_s"])

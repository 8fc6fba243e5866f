"""Share of the chip's bf16 peak over the whole traced stretch: the
needed operations of every prompt token prefilled and every output token
committed in it, over the stretch's length times the peak.  It bounds
any kernel's claim: a kernel taken off the path leaves its roofline
silent, but not this."""
LAYER = "device (TPU v5e)"
UNIT = "%"
MOVES = "output_tok_s"


def read(record, trace):
    led = record["ledger"]
    flops = led["prefill_flops"] + led["decode_flops"]
    if not trace.has_device or trace.window_s <= 0 or not flops:
        return None
    return 100.0 * flops / (trace.window_s
                            * record["peaks"]["bf16_flops_per_s"])

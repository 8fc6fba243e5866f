"""The paged prefill attention kernel's share of its roofline: the least
time the chip could take for the needed work (max of operations over
peak FLOP/s and bytes over peak bandwidth, from
``bench.harness.work.prefill_attn_work``) over the summed device time of
the kernel's events.  The kernel shows in the trace as the custom call
``%paged_prefill_attn_kernel.N``."""
LAYER = "kernels (kernels/paged_attn)"
UNIT = "%"
MOVES = "ttft_p95_s"
OP = r"^%paged_prefill_attn_kernel[.\d]* = .*custom-call"


def read(record, trace):
    t = trace.op_s(OP)
    led, pk = record["ledger"], record["peaks"]
    if t <= 0 or not led["prefill_attn_flops"]:
        return None
    least = max(led["prefill_attn_flops"] / pk["bf16_flops_per_s"],
                led["prefill_attn_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t

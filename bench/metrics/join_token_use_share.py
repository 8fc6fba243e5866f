"""Share of the rows the join computes that hold prompt tokens: over the
program's ``serve.join`` spans in the traced stretch, 100 x the summed
``tokens`` (the prompt tokens the join's pieces carry) over the summed
``rows_computed`` x ``width`` (the rows it computes, each padded to the
round's width).  Padding, rows masked out of a group and a width above
the longest piece all lower it."""
LAYER = "scheduler join"
UNIT = "%"
MOVES = "tpot_p95_ms"
SPAN = "serve.join"


def read(record, trace):
    args = [s[3] for s in trace.program_spans(SPAN)]
    computed = sum(a["rows_computed"] * a["width"] for a in args)
    if not computed:
        return None
    return 100.0 * sum(a["tokens"] for a in args) / computed

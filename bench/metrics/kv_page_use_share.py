"""Share of the KV pool's mapped token slots that hold live tokens: over
the program's ``serve.pages`` spans in the traced stretch (one a round,
before its decode segment), 100 x the summed ``live_tokens`` (the tokens
the live slots hold) over the summed ``mapped_tokens`` (the pool's pages
in use, in tokens).  Pages reserved at admission for tokens not yet
decoded, and the unfilled tail of each slot's last page, lower it."""
LAYER = "pool (serve/kvpool.py)"
UNIT = "%"
MOVES = "output_tok_s"
SPAN = "serve.pages"


def read(record, trace):
    args = [s[3] for s in trace.program_spans(SPAN)]
    mapped = sum(a["mapped_tokens"] for a in args)
    if not mapped:
        return None
    return 100.0 * sum(a["live_tokens"] for a in args) / mapped

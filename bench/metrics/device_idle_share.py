"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals / the stretch), averaged over the
chips."""
LAYER = "device (TPU v5e)"
UNIT = "%"
MOVES = "tpot_p95_ms"


def read(record, trace):
    if not trace.has_device or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)

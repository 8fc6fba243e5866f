"""95th percentile of the time requests waited in the scheduler's queue
before admission, from the program's own registry (``lat.queue_wait_s``,
reset when the window opens; host clock, measured where the wait ends)."""
import numpy as np

LAYER = "scheduler (serve/scheduler.py)"
UNIT = "s"
MOVES = "ttft_p95_s"


def read(record, trace):
    waits = record["queue_waits"]
    return float(np.percentile(waits, 95)) if waits else None

"""Engine: 95th percentile, over requests due in the window, of the time
from the due time to the end of the poll after which the request had left
the waiting queue (harness clock)."""
from chipbench.readers import p95


def read(rec):
    return p95(rec["queue_wait_ms"])

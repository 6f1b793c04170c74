"""Device: int8 operations the window's tokens need (linears, used lm_head
rows, attention over live context) over the window at the int8 peak."""
from chipbench.readers import step_mfu


def read(rec):
    return step_mfu(rec)

"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window)."""
from chipbench.readers import idle_share


def read(rec):
    return idle_share(rec)

"""Integer forward: mean device time of one prefill chunk program
(``jit_prefill``) in the traced window."""
from chipbench.readers import module_ms


def read(rec):
    return module_ms(rec, "jit_prefill")

"""Integer forward: mean device time of one decode step program
(``jit_decode_step``) in the traced window."""
from chipbench.readers import module_ms


def read(rec):
    return module_ms(rec, "jit_decode_step")

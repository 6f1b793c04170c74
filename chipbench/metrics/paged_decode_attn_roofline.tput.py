"""Kernels: paged decode attention's share of its roofline (live KV rows
read once, work.py) over the kernel's device time."""
from chipbench.readers import roofline

PATTERN = r"^paged_decode_qattention"


def read(rec):
    return roofline(rec, "paged_decode_attn", PATTERN)

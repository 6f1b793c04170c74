"""Kernels: paged prefill attention's share of its roofline (causal pairs,
each key row read once, work.py) over the kernel's device time."""
from chipbench.readers import roofline

PATTERN = r"^paged_prefill_qattention"


def read(rec):
    return roofline(rec, "paged_prefill_attn", PATTERN)

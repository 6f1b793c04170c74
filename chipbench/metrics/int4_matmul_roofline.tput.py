"""Kernels: the int4 matmul kernels' share of their roofline: least time the
window's int4 linears need (work.py) over the kernels' device time."""
from chipbench.readers import roofline

PATTERN = r"^int4_matmul$"


def read(rec):
    return roofline(rec, "int4_matmul", PATTERN)

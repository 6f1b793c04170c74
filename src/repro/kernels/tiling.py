"""Tile-size helpers shared by the Pallas kernels."""
from __future__ import annotations


def divisor_tile(cap: int, n: int) -> int:
    """Largest tile <= ``cap`` that divides ``n`` exactly (Pallas grids
    must tile their axis without remainder).  Shared by the attention
    kernels' block-size fallbacks."""
    cap = min(cap, n)
    while n % cap:
        cap -= 1
    return cap

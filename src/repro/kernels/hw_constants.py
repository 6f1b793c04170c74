"""Per-chip roofline ceilings, keyed by JAX's ``device_kind``.

``kernels/autotune.py`` (tile selection), ``benchmarks/roofline.py``
(artifact pricing) and ``repro/analysis/pallas_lint.py`` (VMEM budget
lint) all reason about the same machine; each reads its entry from this
table and never re-declares a number.  The drift test
(``tests/test_autotune.py``) pins every consumer to these objects.

A device kind missing from the table is an error, not a default: a
roofline share priced against another chip's peaks is a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    """Published per-chip peaks, plus the kernels' VMEM budget on it."""
    peak_int8_ops: float     # int8 MXU ops/s
    peak_bf16_flops: float   # bf16 MXU FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per ICI link
    ici_links: int           # links per chip
    vmem_budget: int         # bytes/core a kernel may use (scoped default)


# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s of ICI
# (4 links x 50 GB/s).  The 16 MiB VMEM budget is Mosaic's default scoped
# limit on v5e, not the physical VMEM size.
V5E = Chip(peak_int8_ops=393e12, peak_bf16_flops=197e12, hbm_bw=819e9,
           ici_bw=50e9, ici_links=4, vmem_budget=16 * 2**20)

CHIPS = {"TPU v5 lite": V5E}   # jax.devices()[i].device_kind -> Chip


def chip(device_kind: str) -> Chip:
    """The table entry for ``device_kind``; raises on a kind it lacks."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add its "
            f"entry to repro.kernels.hw_constants.CHIPS (known: "
            f"{sorted(CHIPS)})") from None


# modelling knobs of the tile cost model, not chip data
VMEM_FILL = 0.5              # headroom for double-buffering + scratch
STEP_OVERHEAD_S = 2e-6       # DMA issue + grid step bookkeeping

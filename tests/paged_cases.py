"""Paged decode cases shared by the int8 and int4-pool kernel tests: slot
lengths named against the kernel's page run, at reduced cell geometries."""
import pytest

# reduced cell geometries (hkv, g, d, page, max_blocks): the qwen3-4b decode
# cell's kv heads, group and head dim, and the stablelm long-context cell's,
# with shorter block tables
DECODE_GEOM = (8, 4, 128, 16, 20)
LONGCTX_GEOM = (32, 1, 64, 16, 12)


def page_run_lengths(geom, spec, row=None):
    """Slot lengths named against the paged kernel's page run: "page" one
    page, "run" the pages of one grid step, "run+1" one row past them,
    "table" the whole table.  ``row`` is the pool's row width (the head
    dim, or half of it for the int4 pool)."""
    from repro.kernels import decode_attention as DA
    hkv, _, d, psize, nb = geom
    ppb = DA.pages_per_step(
        DA.page_vmem_bytes((1, hkv, psize, row or d), 1), nb)
    named = {"page": psize, "run": ppb * psize, "run+1": ppb * psize + 1,
             "table": nb * psize}
    return [named.get(x, x) for x in spec]


PAGE_RUN_CASES = [
    pytest.param(geom[3], [ln], geom, id=f"{name}-{ln}")
    for name, geom in (("decode", DECODE_GEOM), ("longctx", LONGCTX_GEOM))
    for ln in (1, "page", "run", "run+1", "table")
] + [
    # slots at very different lengths in one call, an empty one among them
    pytest.param(16, ["table", 1, 0, "run+1", "page", "run"], DECODE_GEOM,
                 id="decode-mixed"),
    pytest.param(16, ["run+1", 0, "table", 1, "page"], LONGCTX_GEOM,
                 id="longctx-mixed"),
]

"""Static checks over the Pallas attention kernels' BlockSpecs.

The kernels' correctness story leans on three structural claims that
nothing machine-checked until now:

``IDXMAP-RANGE`` / ``IDXMAP-CLAMP``
    The KV page addresses stay in range and dead blocks cost no copy: for
    EVERY grid point, over a battery of edge-case lengths / chunk origins /
    block tables, the returned block coordinates must address inside the
    backing array, and every *dead* step must re-address the block already
    resident (that identity is why the pipeliner skips the DMA — an
    out-of-range or merely-different address silently streams garbage or
    wastes bandwidth).  The paged decode kernels' pages must besides be
    the slot's own live pages, and the pages they copy themselves exactly
    the live ones, once each.  The maps are module-level factories
    (``decode_kv_index_map`` / ``paged_kv_index_map`` /
    ``paged_step_pages`` / ``prefill_kv_index_map``) precisely so this
    lint can evaluate them.

``VMEM-BUDGET``
    The per-grid-step VMEM working set implied by the BlockSpec geometry
    (double-buffered KV tiles + q/out tiles + LUT + scratch) must fit the
    shared ``kernels/hw_constants`` budget at the tile sizes
    ``kernels/autotune`` actually picks, and the paged decode kernels'
    two steps of pages at the run length they derive from the shapes —
    the tuner's quick filter only models the KV tiles, so this is the
    check that scratch growth can't sneak past it.

``SCALAR-PREFETCH``
    ``PrefetchScalarGridSpec(num_scalar_prefetch=N)`` makes the FIRST N
    positional operands of the pallas_call the scalar args, in order; the
    index maps then receive them in that same order.  A swapped pair
    (lengths vs block_tables) type-checks and runs — reading garbage.
    Checked via AST against the per-kernel expected name order.

``SHARED-BODY``
    The int8 and int4-packed wrappers claim byte-identical datapaths: both
    must reach the shared ``_paged_decode_body`` / ``_prefill_body``
    through the AST call graph (and every decode kernel the one per-block
    carry update, ``_kv_block_update``), and both paged wrappers must
    build their page addresses from the shared factories rather than a
    local re-derivation.
"""
from __future__ import annotations

import ast
import dataclasses
import inspect
import itertools
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.analysis.jaxpr_audit import Violation
from repro.kernels import autotune
from repro.kernels import decode_attention as DA
from repro.kernels import prefill_attention as PA
from repro.kernels.hw_constants import V5E, VMEM_FILL

VMEM_BUDGET = V5E.vmem_budget    # the kernels' target chip

LUT_BYTES = 512 * 4          # exp LUT tile (LUT_SIZE int32 in VMEM)


@dataclasses.dataclass
class Check:
    check: str
    kernel: str
    ok: bool
    detail: str

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def _violation(rule: str, kernel: str, detail: str) -> Violation:
    return Violation(rule=rule, graph=f"pallas:{kernel}", scope="", detail=detail)


# --- index-map bounds ----------------------------------------------------

def check_decode_kv_map(map_factory: Callable = DA.decode_kv_index_map,
                        *, b: int = 3, hkv: int = 2, smax: int = 64,
                        bkv: int = 16,
                        kernel: str = "decode_qattention") -> List[Violation]:
    """Contiguous decode map: (bb, blk, h, 0) must stay inside
    (B, Smax//bkv, Hkv) for every grid point and every length in
    [0, smax], and dead steps must re-address the last live block."""
    out: List[Violation] = []
    nblk = smax // bkv
    kv_map = map_factory(bkv)
    for lens_val in (0, 1, bkv - 1, bkv, bkv + 1, smax - 1, smax):
        lens = np.full((b,), lens_val, np.int32)
        last_live = max((lens_val - 1) // bkv, 0)
        for bb, h, k in itertools.product(range(b), range(hkv), range(nblk)):
            bb_o, blk, h_o, r = (int(x) for x in kv_map(bb, h, k, lens))
            if not (bb_o == bb and 0 <= blk < nblk and h_o == h and r == 0):
                out.append(_violation(
                    "IDXMAP-RANGE", kernel,
                    f"len={lens_val} grid=({bb},{h},{k}) -> "
                    f"({bb_o},{blk},{h_o},{r}) outside (B,{nblk},Hkv)"))
            elif k * bkv >= lens_val and blk != last_live:
                out.append(_violation(
                    "IDXMAP-CLAMP", kernel,
                    f"dead step k={k} (len={lens_val}) addresses block "
                    f"{blk}, not the last live block {last_live}"))
    return out


def _example_btab(b: int, nb: int, n_pages: int,
                  live_blocks: int) -> np.ndarray:
    """A representative allocator state: each slot owns ``live_blocks``
    distinct non-trash pages (strided so slots interleave), zeros (the
    trash page) beyond its chain — exactly what the engine hands the
    kernels."""
    btab = np.zeros((b, nb), np.int32)
    nxt = 1
    for bb in range(b):
        for k in range(live_blocks):
            btab[bb, k] = 1 + (nxt % (n_pages - 1))
            nxt += 3
    return btab


def _distinct_btab(b: int, nb: int, lens: np.ndarray,
                   psize: int) -> np.ndarray:
    """Each slot's live blocks on distinct, non-monotone pages (no page
    shared between slots, so a page of the wrong slot cannot pass), the
    trash page 0 beyond its chain."""
    btab = np.zeros((b, nb), np.int32)
    for bb in range(b):
        for k in range(-(-int(lens[bb]) // psize)):
            btab[bb, k] = 1 + (bb * nb + k) * 7 % (b * nb)
    return btab


def check_paged_decode_kv_map(map_factory: Callable = DA.paged_kv_index_map,
                              step_factory: Callable = DA.paged_step_pages,
                              *, b: int = 4, nb: int = 11, psize: int = 16,
                              ppb: int = 4,
                              kernel: str = "paged_decode_qattention",
                              ) -> List[Violation]:
    """Both page-address helpers of the paged decode kernels, over slots of
    edge-case lengths side by side in one table:

    * the pages a step copies itself (``step_factory``): each a live block
      of the slot's own table row at its own offset, inside the pool; a
      step past the slot's length copies nothing; every live block is
      copied exactly once;
    * the page block operands (``map_factory``): every page addressed is
      one of the slot's own live pages inside the pool (its first table
      entry for an empty slot); a live block addresses exactly its page;
      past the slot's length an operand keeps the page it already holds,
      so no copy is issued."""
    out: List[Violation] = []
    n_pages = b * nb + 1
    nsteps = -(-nb // ppb)
    steps = step_factory(psize, ppb)
    maps = [map_factory(psize, ppb, i) for i in range(ppb)]
    battery = (0, 1, psize - 1, psize, psize + 1, ppb * psize,
               ppb * psize + 1, nb * psize - 1, nb * psize)
    for rot in range(len(battery)):
        lens = np.asarray([battery[(rot + bb) % len(battery)]
                           for bb in range(b)], np.int32)
        btab = _distinct_btab(b, nb, lens, psize)
        for bb in range(b):
            live = -(-int(lens[bb]) // psize)
            own = {int(btab[bb, k]) for k in range(max(live, 1))}
            copied = []
            for j in range(nsteps):
                count, page = steps(bb, j, lens, btab)
                count = int(count)
                if j * ppb >= live and count:
                    out.append(_violation(
                        "IDXMAP-CLAMP", kernel,
                        f"len={lens[bb]} step {j} past the slot's length "
                        f"copies {count} page(s)"))
                for i in range(count):
                    blk = j * ppb + i
                    try:
                        pg = int(page(i))
                    except IndexError:            # read past the table row
                        pg = -1
                    copied.append(blk)
                    if not (0 <= pg < n_pages and blk < live
                            and pg == int(btab[bb, blk])):
                        out.append(_violation(
                            "IDXMAP-RANGE", kernel,
                            f"len={lens[bb]} step {j} copies page {pg} as "
                            f"block {blk}: not the slot's own live page "
                            f"of a pool of {n_pages}"))
            if copied != list(range(live)):
                out.append(_violation(
                    "IDXMAP-RANGE", kernel,
                    f"len={lens[bb]} copies blocks {copied}, not each of "
                    f"the {live} live blocks once in order"))
            for i, kv_map in enumerate(maps):
                held = None
                for j in range(nsteps):
                    pg, *rest = (int(x) for x in kv_map(bb, j, lens, btab))
                    blk = j * ppb + i
                    if not (0 <= pg < n_pages and rest == [0, 0, 0]
                            and pg in own):
                        out.append(_violation(
                            "IDXMAP-RANGE", kernel,
                            f"len={lens[bb]} grid=({bb},{j}) operand {i} -> "
                            f"page {pg}: not one of the slot's own live "
                            f"pages of a pool of {n_pages}"))
                    elif blk < live and pg != int(btab[bb, blk]):
                        out.append(_violation(
                            "IDXMAP-RANGE", kernel,
                            f"len={lens[bb]} grid=({bb},{j}) operand {i} -> "
                            f"page {pg}, live block {blk} is on page "
                            f"{int(btab[bb, blk])}"))
                    elif blk >= live and held is not None and pg != held:
                        out.append(_violation(
                            "IDXMAP-CLAMP", kernel,
                            f"dead step {j} (len={lens[bb]}) operand {i} "
                            f"addresses page {pg}, not the resident {held}"))
                    held = pg
    return out


def check_prefill_kv_map(map_factory: Callable = PA.prefill_kv_index_map,
                         *, b: int = 2, h: int = 4, group: int = 2,
                         nb: int = 4, psize: int = 16, bq: int = 8,
                         sq: int = 16, n_pages: int = 13,
                         kernel: str = "paged_prefill_qattention",
                         ) -> List[Violation]:
    """Paged prefill map under the kernel contract ``pos0 + sq <= nb *
    psize`` (page-aligned chunks): page in-pool, kv head = q head // group,
    and blocks past the q-block's causal frontier re-address the frontier
    page."""
    out: List[Violation] = []
    kv_map = map_factory(bq, psize, group)
    nq = sq // bq
    hkv = h // group
    for pos0_val in (0, psize, nb * psize - sq):
        live_blocks = max(-(-(pos0_val + sq) // psize), 1)
        btab = _example_btab(b, nb, n_pages, live_blocks)
        pos0 = np.full((b,), pos0_val, np.int32)
        for bb, hh, qi, ki in itertools.product(
                range(b), range(h), range(nq), range(nb)):
            frontier = (pos0_val + (qi + 1) * bq - 1) // psize
            pg, h_o, r0, r1 = (int(x)
                               for x in kv_map(bb, hh, qi, ki, pos0, btab))
            if not (0 <= pg < n_pages and r0 == 0 and r1 == 0
                    and 0 <= h_o < hkv):
                out.append(_violation(
                    "IDXMAP-RANGE", kernel,
                    f"pos0={pos0_val} grid=({bb},{hh},{qi},{ki}) -> "
                    f"(page {pg}, head {h_o}) outside "
                    f"(pool {n_pages}, Hkv {hkv})"))
            elif h_o != hh // group:
                out.append(_violation(
                    "IDXMAP-RANGE", kernel,
                    f"q head {hh} mapped to kv head {h_o}, "
                    f"expected {hh // group}"))
            elif pg != int(btab[bb, min(ki, frontier)]):
                out.append(_violation(
                    "IDXMAP-CLAMP", kernel,
                    f"pos0={pos0_val} grid=({bb},{hh},{qi},{ki}) -> page "
                    f"{pg}, expected frontier-clamped "
                    f"{int(btab[bb, min(ki, frontier)])}"))
    return out


# --- VMEM tile budgets ---------------------------------------------------

def _decode_tile_bytes(g: int, d: int, kv_tile_rows: int,
                       kv_bits: int) -> int:
    """VMEM working set of one decode grid step from the BlockSpec
    geometry: double-buffered K+V tiles, q + out tiles, LUT, and the three
    scratch buffers ((g,128) i32 + (g,128) f32 + (g,d) f32)."""
    kv_row = d // 2 if kv_bits == 4 else d
    kv = 2 * 2 * kv_tile_rows * kv_row            # K+V, double-buffered
    q_out = 2 * g * d
    scratch = g * 128 * 4 + g * 128 * 4 + g * d * 4
    return kv + q_out + LUT_BYTES + scratch


def _paged_decode_tile_bytes(hkv: int, g: int, hd: int, page: int, nb: int,
                             kv_bits: int):
    """(pages per step, VMEM working set of one paged decode grid step):
    the K and V pages of two steps (one computing, one arriving), q and
    out blocks double-buffered, the LUT, and the three carries."""
    row = hd // 2 if kv_bits == 4 else hd
    page_bytes = DA.page_vmem_bytes((1, hkv, page, row), 1)
    ppb = DA.pages_per_step(page_bytes, nb)
    rows, lanes = hkv * g, -(-hd // 128) * 128
    kv = 2 * 2 * ppb * page_bytes
    q_out = 2 * 2 * rows * lanes
    scratch = 2 * rows * 128 * 4 + rows * lanes * 4
    return ppb, kv + q_out + LUT_BYTES + scratch


def _prefill_tile_bytes(bq: int, d: int, psize: int, kv_bits: int) -> int:
    kv_row = d // 2 if kv_bits == 4 else d
    kv = 2 * 2 * psize * kv_row
    q_out = 2 * bq * d
    scratch = bq * 128 * 4 + bq * 128 * 4 + bq * d * 4
    return kv + q_out + LUT_BYTES + scratch


# (name, geometry) battery: the audit presets' smoke shape plus a
# deployment-scale shape, both bit widths
_DECODE_SHAPES = (
    ("smoke", dict(smax=64, batch_slots=4, hkv=4, hd=32, kv_bits=8)),
    ("large", dict(smax=4096, batch_slots=64, hkv=8, hd=128, kv_bits=8)),
    ("large_kv4", dict(smax=4096, batch_slots=64, hkv=8, hd=128, kv_bits=4)),
)
# the benchmark cells' paged decode shapes, the int4 pool, and the local
# head slice of a four-way tensor-parallel qwen3-4b
_PAGED_DECODE_SHAPES = (
    ("qwen3-4b.decode", dict(hkv=8, g=4, hd=128, page=16, nb=96, kv_bits=8)),
    ("qwen3-4b.decode_kv4", dict(hkv=8, g=4, hd=128, page=16, nb=96,
                                 kv_bits=4)),
    ("stablelm-1.6b.longctx", dict(hkv=32, g=1, hd=64, page=16, nb=256,
                                   kv_bits=8)),
    ("qwen3-4b.tp4", dict(hkv=2, g=4, hd=128, page=16, nb=96, kv_bits=8)),
)
_PREFILL_SHAPES = (
    ("smoke", dict(sq=32, batch_slots=4, page_size=16, hkv=4, hd=32,
                   kv_bits=8, n_blocks=4, n_heads=4)),
    ("large", dict(sq=512, batch_slots=16, page_size=64, hkv=8, hd=128,
                   kv_bits=8, n_blocks=64, n_heads=32)),
    ("large_kv4", dict(sq=512, batch_slots=16, page_size=64, hkv=8, hd=128,
                       kv_bits=4, n_blocks=64, n_heads=32)),
)


def check_vmem_budgets() -> List[Violation]:
    """At the tile sizes autotune actually picks for a battery of shapes,
    the full BlockSpec working set (not just the tuner's KV-tile filter)
    must fit the shared VMEM budget."""
    out: List[Violation] = []
    for tag, kw in _DECODE_SHAPES:
        bkv = autotune.decode_bkv(kw["smax"], batch_slots=kw["batch_slots"],
                                  hkv=kw["hkv"], hd=kw["hd"],
                                  kv_bits=kw["kv_bits"])
        g = 8    # worst-case GQA group sharing one kv head's tile
        used = _decode_tile_bytes(g, kw["hd"], bkv, kw["kv_bits"])
        if used > VMEM_BUDGET * VMEM_FILL:
            out.append(_violation(
                "VMEM-BUDGET", f"decode[{tag}]",
                f"bkv={bkv} working set {used}B exceeds "
                f"{int(VMEM_BUDGET * VMEM_FILL)}B "
                f"(VMEM_BUDGET*VMEM_FILL) at {kw}"))
    for tag, kw in _PAGED_DECODE_SHAPES:
        ppb, used = _paged_decode_tile_bytes(**kw)
        if used > VMEM_BUDGET * VMEM_FILL:
            out.append(_violation(
                "VMEM-BUDGET", f"paged_decode[{tag}]",
                f"ppb={ppb} working set {used}B exceeds "
                f"{int(VMEM_BUDGET * VMEM_FILL)}B "
                f"(VMEM_BUDGET*VMEM_FILL) at {kw}"))
    for tag, kw in _PREFILL_SHAPES:
        bq = autotune.prefill_bq(kw["sq"], batch_slots=kw["batch_slots"],
                                 page_size=kw["page_size"], hkv=kw["hkv"],
                                 hd=kw["hd"], kv_bits=kw["kv_bits"],
                                 n_blocks=kw["n_blocks"],
                                 n_heads=kw["n_heads"])
        used = _prefill_tile_bytes(bq, kw["hd"], kw["page_size"],
                                   kw["kv_bits"])
        if used > VMEM_BUDGET * VMEM_FILL:
            out.append(_violation(
                "VMEM-BUDGET", f"prefill[{tag}]",
                f"bq={bq} working set {used}B exceeds "
                f"{int(VMEM_BUDGET * VMEM_FILL)}B "
                f"(VMEM_BUDGET*VMEM_FILL) at {kw}"))
    return out


# --- AST checks: scalar-prefetch ordering + shared-body diff gate --------

# kernel -> (module, expected scalar operand names, in pallas_call order)
SCALAR_PREFETCH_ORDER = {
    "decode_qattention": (DA, ("lengths",)),
    "_paged_call": (DA, ("lengths", "block_tables")),   # both paged wrappers
    "paged_prefill_qattention": (PA, ("pos0", "block_tables")),
    "paged_prefill_qattention_q4": (PA, ("pos0", "block_tables")),
}

# kernel fn -> (module, shared body it must reach transitively)
SHARED_BODY = {
    "_decode_kernel": (DA, "_kv_block_update"),
    "_paged_decode_kernel": (DA, "_paged_decode_body"),
    "_paged_decode_q4_kernel": (DA, "_paged_decode_body"),
    "_paged_decode_body": (DA, "_kv_block_update"),
    "_paged_prefill_kernel": (PA, "_prefill_body"),
    "_paged_prefill_q4_kernel": (PA, "_prefill_body"),
}

# public wrapper -> (module, the page-address factories it must use)
INDEX_MAP_FACTORY = {
    "decode_qattention": (DA, ("decode_kv_index_map",)),
    "paged_decode_qattention": (DA, ("paged_step_pages",
                                     "paged_kv_index_map")),
    "paged_decode_qattention_q4": (DA, ("paged_step_pages",
                                        "paged_kv_index_map")),
    "paged_prefill_qattention": (PA, ("prefill_kv_index_map",)),
    "paged_prefill_qattention_q4": (PA, ("prefill_kv_index_map",)),
}

_mod_ast_cache: Dict[str, ast.Module] = {}
_mod_src_cache: Dict[str, str] = {}


def _module_ast(mod) -> ast.Module:
    if mod.__name__ not in _mod_ast_cache:
        src = inspect.getsource(mod)
        _mod_src_cache[mod.__name__] = src
        _mod_ast_cache[mod.__name__] = ast.parse(src)
    return _mod_ast_cache[mod.__name__]


def _find_funcdef(mod, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(_module_ast(mod)):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def check_scalar_prefetch() -> List[Violation]:
    """The first ``num_scalar_prefetch`` positional operands of each
    kernel's pallas_call must name the expected scalars in order."""
    out: List[Violation] = []
    for kernel, (mod, expected) in SCALAR_PREFETCH_ORDER.items():
        fd = _find_funcdef(mod, kernel)
        if fd is None:
            out.append(_violation("SCALAR-PREFETCH", kernel,
                                  "kernel function not found"))
            continue
        src = _mod_src_cache[mod.__name__]
        nsp = None
        operands: Optional[Sequence[ast.expr]] = None
        for node in ast.walk(fd):
            if isinstance(node, ast.Call) \
                    and _call_name(node) == "PrefetchScalarGridSpec":
                for kwarg in node.keywords:
                    if kwarg.arg == "num_scalar_prefetch" \
                            and isinstance(kwarg.value, ast.Constant):
                        nsp = kwarg.value.value
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Call) \
                    and _call_name(node.func) == "pallas_call":
                operands = node.args
        if nsp is None or operands is None:
            out.append(_violation(
                "SCALAR-PREFETCH", kernel,
                "could not locate PrefetchScalarGridSpec"
                f"(num_scalar_prefetch=...) + pallas_call(...)(operands) "
                f"in {kernel}"))
            continue
        if nsp != len(expected):
            out.append(_violation(
                "SCALAR-PREFETCH", kernel,
                f"num_scalar_prefetch={nsp} but {len(expected)} scalar "
                f"operands expected ({', '.join(expected)})"))
            continue
        for i, want in enumerate(expected):
            seg = ast.get_source_segment(src, operands[i]) or ""
            if want not in seg:
                out.append(_violation(
                    "SCALAR-PREFETCH", kernel,
                    f"scalar operand {i} is `{seg.strip()}`, expected it "
                    f"to carry `{want}` (order: {', '.join(expected)})"))
    return out


def _reaches(mod, fn_name: str, target: str,
             seen: Optional[set] = None) -> bool:
    if fn_name == target:
        return True
    seen = seen or set()
    if fn_name in seen:
        return False
    seen.add(fn_name)
    fd = _find_funcdef(mod, fn_name)
    if fd is None:
        return False
    callees = set()
    for node in ast.walk(fd):
        if isinstance(node, ast.Call):
            callees.add(_call_name(node))
        elif isinstance(node, ast.Name):
            # functools.partial(_decode_kernel, ...) and bare references
            callees.add(node.id)
    return any(_reaches(mod, c, target, seen)
               for c in callees if c != fn_name)


def check_shared_body() -> List[Violation]:
    """Every kernel wrapper must reach the shared audited body; every
    public wrapper must build its KV map from the shared factory."""
    out: List[Violation] = []
    for fn_name, (mod, body) in SHARED_BODY.items():
        if not _reaches(mod, fn_name, body):
            out.append(_violation(
                "SHARED-BODY", fn_name,
                f"does not dispatch into the shared `{body}` — the "
                "int8/int4 byte-identity claim no longer holds"))
    for fn_name, (mod, factories) in INDEX_MAP_FACTORY.items():
        fd = _find_funcdef(mod, fn_name)
        local = fd is not None and any(
            isinstance(n, ast.FunctionDef) and n.name == "kv_map"
            for n in ast.walk(fd))
        for factory in factories:
            used = fd is not None and any(
                isinstance(n, ast.Call) and _call_name(n) == factory
                for n in ast.walk(fd))
            if not used or local:
                out.append(_violation(
                    "SHARED-BODY", fn_name,
                    f"KV page addresses must come from the shared "
                    f"`{factory}` factory (no local kv_map re-derivations)"))
    return out


def run_all() -> Dict:
    """Every pallas lint; returns {"checks": [...], "violations": [...]}."""
    groups = {
        "idxmap_decode": check_decode_kv_map(),
        "idxmap_paged_decode": check_paged_decode_kv_map(),
        "idxmap_prefill": check_prefill_kv_map(),
        "vmem_budget": check_vmem_budgets(),
        "scalar_prefetch": check_scalar_prefetch(),
        "shared_body": check_shared_body(),
    }
    checks = [Check(check=name, kernel="*", ok=not viols,
                    detail=f"{len(viols)} violation(s)").to_dict()
              for name, viols in groups.items()]
    return {"checks": checks,
            "violations": [v.to_dict() for vs in groups.values()
                           for v in vs]}

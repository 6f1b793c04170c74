"""Batched single-query decode attention over the int8 KV cache — the
continuous-batching serving kernels.

Per KV block the datapath is exactly the paper's Softmax Core —

    int8 q @ kᵀ -> int32 scores -> (max - s) -> fixed-point LUT index ->
    Q0.7 exp numerators -> int8 P @ int8 V on the MXU -> int32 partial

— with the same fp32 cross-block carry (running max rescale, denominator,
output accumulator) as ``flash_qattention``.  ``_kv_block_update`` is that
one block's step, and every kernel here calls it.

Contiguous cache (``decode_qattention``): one grid step = (slot, kv head,
KV block); the BlockSpec index map clamps dead blocks onto the slot's last
live one, so the pipeliner issues no DMA for them.  With a single KV block
it is the paper's row-wise softmax, bit-exact vs
``kernels/ref.py::decode_qattention_ref``.

Paged pool (``paged_decode_qattention`` and its int4 twin): one grid step =
(slot, run of ``ppb`` pages) across ALL the slot's kv heads, ``ppb``
derived from the page's bytes.  A page is one contiguous (Hkv, P, D)
block of the head-major pool, addressed through the slot's
scalar-prefetched block-table row.  Where the pool's rows fill whole
128-lane vregs the kernel copies the step's live pages itself, into a
double-buffered VMEM window, and starts the next live step's copies (the
next slot's when this one is done) before it computes; a narrower pool
(head dim 64, the int4 pool's packed rows), which Mosaic cannot slice in
HBM, comes as one block operand a page through the pipeliner, whose index
maps hold a dead page on the block already resident.  Either way pages
past the slot's length are never copied, and a step with no live page
does no compute.  A score row lays ``hg`` heads' keys side by side in 128
lanes, so one page of every head is one MXU product and a few full vregs;
the pages are folded into the carry one at a time, in table order, so the
result is bit-exact vs ``ref.py::paged_decode_qattention_ref`` for any
``ppb``.

GQA: q heads arrive pre-grouped per kv head, (B, Hkv, G, D); each live KV
block is read from HBM once, shared by the whole group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint as fxp
from repro.core.qsoftmax import LUT_SIZE, MASK_OFFSET
from repro.kernels.hw_constants import V5E, VMEM_FILL
from repro.kernels.tiling import divisor_tile
from repro.kernels.quant_softmax import lut_lookup

NEG_INIT = -(1 << 30)
INT32_MIN = -(1 << 31)

# K+V bytes one grid step of the paged kernels covers: enough pages that
# the fixed cost of a step is spread thin, two steps in flight far inside
# the VMEM budget.  A step computes all its pages (the dead ones of a
# slot's last run are left out of the carry), so the run is also capped
MAX_PAGES_PER_STEP = 16
STEP_KV_BYTES = 512 * 1024


def decode_kv_index_map(bkv):
    """KV BlockSpec index map for the CONTIGUOUS decode kernel.

    Clamps dead KV blocks onto the slot's last live block: the dead grid
    step re-addresses the block already resident in VMEM, so the pipeliner
    issues no DMA.  Module-level (not a closure inside the wrapper) so
    ``repro.analysis.pallas_lint`` can evaluate its bounds over the grid."""
    def kv_map(bb, h, k, lens):
        last_live = jnp.maximum((lens[bb] - 1) // bkv, 0)
        return (bb, jnp.minimum(k, last_live), h, 0)
    return kv_map


def pages_per_step(page_bytes, max_blocks):
    """Pages one grid step of a paged decode kernel covers, from the VMEM
    bytes of one page across the kernel's kv heads (K or V alone): as many
    as fit ``STEP_KV_BYTES`` of K+V, no more than the table holds or
    ``MAX_PAGES_PER_STEP``, with both buffers of K and V inside the VMEM
    budget."""
    fit = int(V5E.vmem_budget * VMEM_FILL) // (2 * 2 * page_bytes)
    return max(1, min(max_blocks, MAX_PAGES_PER_STEP,
                      STEP_KV_BYTES // (2 * page_bytes), fit))


def page_vmem_bytes(pool_shape, itemsize):
    """VMEM bytes of one page of a (n_pages, Hkv, P, row) pool: rows are
    padded to whole 128-lane vregs (a 64-wide head dim holds 128)."""
    _, hkv, psize, row = pool_shape
    return hkv * psize * pl.cdiv(row, 128) * 128 * itemsize


def paged_kv_index_map(psize, ppb, i):
    """Index map of the ``i``-th page operand of BOTH paged decode kernels
    (int8 and int4-packed) over the head-major (n_pages, Hkv, P, D) pool:
    grid step ``(bb, j)`` computes on logical blocks ``j*ppb + i`` of slot
    ``bb``'s table row, and this operand brings the one at offset ``i``.

    While that block is live the map returns its page.  Past the slot's
    length it returns the last live block the operand held, which is
    already resident, so the pipeliner issues no copy; an operand that no
    live block of the slot reaches holds the slot's first page.  One
    factory for both kernels, module-level so ``pallas_lint`` can prove that
    every page addressed is the slot's own, inside the pool and live."""
    def kv_map(bb, j, lens, btab):
        live = (lens[bb] + psize - 1) // psize    # live blocks of the slot
        held = i + (live - 1 - i) // ppb * ppb    # operand's last live block
        k = jnp.where(live > i, jnp.minimum(j * ppb + i, held), 0)
        return (btab[bb, k], 0, 0, 0)
    return kv_map


def paged_step_pages(psize, ppb):
    """Live pages of one grid step, shared by BOTH paged decode kernels:
    step ``step`` of slot ``bb`` covers logical blocks ``[step*ppb,
    step*ppb + ppb)`` of the slot's table row, of which ``count`` are live
    (0 for a step wholly past the slot's length), logical block
    ``step*ppb + i`` in pool page ``page(i)`` for ``i < count``.  Where the
    kernel copies pages itself it copies exactly these.  Module-level so
    ``pallas_lint`` can prove every copied page is the slot's own, inside
    the pool and live."""
    def step_pages(bb, step, lens, btab):
        first = step * ppb
        live = (lens[bb] + psize - 1) // psize
        count = jnp.clip(live - first, 0, ppb)
        return count, lambda i: btab[bb, first + i]
    return step_pages


def dequant_kv_tile(w_u8, scale):
    """Fused in-VMEM dequant of one nibble-planar int4 KV tile.

    (..., D//2) uint8 -> (..., D) int8: sign-extend both nibble planes
    (same branch-free ``(x ^ 8) - 8`` as ``int4_matmul``/``core.packing``),
    concatenate along the head dim (planar layout), multiply by the page's
    shared fp32 scale, round, clip.  Bit-identical to
    ``packing.dequantize_kv_page`` — the oracles depend on it."""
    w = w_u8.astype(jnp.int32)
    lo = ((w & 15) ^ 8) - 8
    hi = (((w >> 4) & 15) ^ 8) - 8
    c4 = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    return jnp.clip(jnp.round(c4 * scale), -127, 127).astype(jnp.int8)


def _kv_block_update(carry, s, kpos, length, own, lookup, pv_of, mi_ref,
                     si_ref, inv_ref):
    """One KV block's step of the decode carry, shared by every decode
    kernel: int32 scores -> mask -> block max -> fixed-point LUT index ->
    Q0.7 numerators -> int8 P @ V -> fp32 running-max rescale of the
    denominator and accumulator.  Returns the new ``(m, den, acc)``.

    ``s`` holds the int32 scores of R query rows against a block of keys at
    positions ``kpos``.  ``own`` (or None: every key) marks the keys of each
    row's own kv head, where a kernel lays several heads' keys side by side;
    the others are left out of the max and get zero numerators.
    ``lookup(idx)`` reads the exp table, ``pv_of(num)`` returns num @ V in
    int32.  Every carried value is per row, so how many rows or heads one
    call covers does not change a bit."""
    m_old, den, acc = carry                       # (R, 1), (R, ·), (R, D)
    s = jnp.where(kpos < length, s, s - MASK_OFFSET)
    if own is None:
        lm = jnp.max(s, axis=-1, keepdims=True)
    else:
        lm = jnp.max(jnp.where(own, s, INT32_MIN), axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, lm)
    idx = jnp.clip(fxp.rescale(m_new - s, mi_ref[0], si_ref[0], out_bits=9),
                   0, LUT_SIZE - 1)
    num = lookup(idx)                                          # Q0.7
    if own is not None:
        num = jnp.where(own, num, 0)
    den_b = jnp.sum(num, axis=-1, keepdims=True)
    pv = pv_of(num)                                            # (R, D) int32
    f = jnp.exp((m_old - m_new).astype(jnp.float32) * inv_ref[0])
    f = jnp.where(m_old == NEG_INIT, 0.0, f)
    return (m_new, den * f + den_b.astype(jnp.float32),
            acc * f + pv.astype(jnp.float32))


def _load_carry(m_scr, den_scr, acc_scr):
    return m_scr[:, :1], den_scr[...], acc_scr[...]


def _store_carry(carry, m_scr, den_scr, acc_scr):
    m, den, acc = carry
    m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
    den_scr[...] = den
    acc_scr[...] = acc


def _lut_gather(idx, lo, hi):
    """Exp-table read as two in-register lane gathers: ``lo``/``hi`` hold
    entries 0-127 / 128-255 broadcast over (R, 128); ``idx`` (R, 128·n)
    in [0, 255].  Same values as ``lut_lookup``'s one-hot select."""
    out = []
    for c in range(0, idx.shape[1], 128):
        ix = idx[:, c:c + 128]
        a = jnp.take_along_axis(lo, ix & 127, axis=1, mode="promise_in_bounds")
        b = jnp.take_along_axis(hi, ix & 127, axis=1, mode="promise_in_bounds")
        out.append(jnp.where(ix >= 128, b, a))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _dot_nt(a, b):
    """int8 a (M, K) @ b (N, K)ᵀ -> int32 (M, N)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _dot_nn(a, b):
    """int8 a (M, K) @ b (K, N) -> int32 (M, N)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _init_carry(m_scr, den_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
    den_scr[...] = jnp.zeros_like(den_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish(osc_ref, den_scr, acc_scr):
    """fp32 epilogue: divide by the denominator, project to the out grid."""
    den = jnp.maximum(den_scr[:, :1], 1.0)
    o = acc_scr[...] / den * osc_ref[0]
    return jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


def _decode_kernel(bkv, len_ref, q_ref, k_ref, v_ref, lut_ref, mi_ref,
                   si_ref, inv_ref, osc_ref, o_ref, m_scr, den_scr, acc_scr):
    b_i = pl.program_id(0)
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_i == 0)
    def _init():
        _init_carry(m_scr, den_scr, acc_scr)

    length = len_ref[b_i]                         # this slot's valid prefix

    @pl.when((k_i * bkv) < length)                # dead blocks: no compute
    def _block():                                 # (and no DMA: the index
        q = q_ref[0, 0]                           # map re-addresses a
        k, v = k_ref[0, :, 0], v_ref[0, :, 0]     # resident block)
        s = _dot_nt(q, k)                         # (G, bkv) int32
        lut = lut_ref[...].astype(jnp.int32)
        carry = _kv_block_update(
            _load_carry(m_scr, den_scr, acc_scr), s,
            k_i * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
            length, None, lambda idx: lut_lookup(idx, lut),
            lambda num: _dot_nn(num.astype(jnp.int8), v),
            mi_ref, si_ref, inv_ref)
        _store_carry(carry, m_scr, den_scr, acc_scr)

    @pl.when(k_i == nk - 1)
    def _epilogue():
        o_ref[0, 0] = _finish(osc_ref, den_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_qattention(
    q_i8: jax.Array,       # int8 (B, Hkv, G, D) — one token/slot, grouped q
    k_i8: jax.Array,       # int8 (B, Smax, Hkv, D) — cache-NATIVE layout
    v_i8: jax.Array,
    lengths: jax.Array,    # int32 (B,): valid cache prefix per slot
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, bkv: int = 512, interpret: bool = False,
) -> jax.Array:
    """Continuous-batching decode attention: int8 (B, Hkv, G, D) context on
    the attn_out grid, each slot masked to its own ``lengths[b]`` prefix.

    K/V come in the cache's native (B, Smax, Hkv, D) layout — the BlockSpec
    index maps gather the (bkv, D) slab per kv head directly, so no per-step
    transpose of the whole cache ever materializes in HBM."""
    b, hkv, g, d = q_i8.shape
    smax = k_i8.shape[1]
    bkv = divisor_tile(bkv, smax)
    grid = (b, hkv, smax // bkv)
    kernel = functools.partial(_decode_kernel, bkv)
    kv_map = decode_kv_index_map(bkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, k, lens: (bb, h, 0, 0)),
            pl.BlockSpec((1, bkv, 1, d), kv_map),
            pl.BlockSpec((1, bkv, 1, d), kv_map),
            pl.BlockSpec((LUT_SIZE,), lambda bb, h, k, lens: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, h, k, lens: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.int32),     # running max (col-broadcast)
            pltpu.VMEM((g, 128), jnp.float32),   # running denominator
            pltpu.VMEM((g, d), jnp.float32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32).reshape(-1),
      q_i8, k_i8, v_i8, lut_q7,
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))


def _heads_per_row(hkv, psize):
    """Kv heads whose pages share one score row of the paged kernels: the
    most that divide ``hkv`` and fit their ``psize`` keys each into 128
    lanes (8 at page 16), so a page of every head is one MXU product and a
    few full vregs, not ``hkv`` slivers."""
    return max(h for h in range(1, hkv + 1)
               if hkv % h == 0 and (h == 1 or h * psize <= 128))


def _head_rows(x, hg, lanes):
    """(Hkv, P, D) page -> (Hkv // hg * lanes, D): each run of ``hg`` heads'
    keys stacked, zero-padded to ``lanes`` rows."""
    hkv, psize, d = x.shape
    x = x.reshape(hkv * psize, d)
    if hg * psize == lanes:
        return x
    x = x.reshape(hkv // hg, hg * psize, d)
    pad = jnp.zeros((hkv // hg, lanes - hg * psize, d), x.dtype)
    return jnp.concatenate([x, pad], axis=1).reshape(hkv // hg * lanes, d)


def _paged_decode_body(psize, ppb, hg, manual, step_pages, kv_load, len_ref,
                       btab_ref, q_ref, *rest):
    # shared body of both paged kernels; they differ only in ``kv_load``
    # (identity vs fused nibble dequant of page ``i`` of the step).
    # Rows are (kv head, group member); the heads come in runs of ``hg``
    # whose keys sit side by side in one ``lanes``-wide score row
    nkv = 2 if manual else 2 * ppb
    kv, (lut_ref, mi_ref, si_ref, inv_ref, osc_ref, o_ref) = (
        rest[:nkv], rest[nkv:nkv + 6])
    copy_refs, (m_scr, den_scr, acc_scr) = rest[nkv + 6:-3], rest[-3:]
    b_i = pl.program_id(0)
    j = pl.program_id(1)
    grid = (b_i, j, pl.num_programs(0), pl.num_programs(1))

    if manual:
        @pl.when((b_i == 0) & (j == 0))
        def _start_grid():                        # no copy in flight
            state = copy_refs[-1]
            state[0] = -1
            state[1] = 0

    @pl.when(j == 0)
    def _init():
        _init_carry(m_scr, den_scr, acc_scr)

    count, _ = step_pages(b_i, j, len_ref, btab_ref)

    @pl.when(count > 0)                           # dead step: no compute
    def _step():
        if manual:
            pages = _copy_pages(grid, step_pages, len_ref, btab_ref, *kv,
                                *copy_refs)
        else:                                     # one block operand a page
            def pages(i):
                return kv[2 * i].at[0], kv[2 * i + 1].at[0]
        q = q_ref[0]                              # (R, D) int8
        rows, _ = q.shape
        rg = rows // pages(0)[0].shape[0] * hg    # rows of one run of heads
        nrun = rows // rg
        lanes = pl.cdiv(hg * psize, 128) * 128
        shape = (rows, lanes)
        r_io = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        c_io = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        own = (r_io % rg) // (rg // hg) == c_io // psize
        run = r_io // rg
        kcol = c_io % psize
        lo = jnp.broadcast_to(lut_ref[0:1, :], (rows, 128))
        hi = jnp.broadcast_to(lut_ref[1:2, :], (rows, 128))
        length = len_ref[b_i]

        def lookup(idx):
            return _lut_gather(idx, lo, hi)

        # every page of the step is computed with no branch between them,
        # so the scheduler overlaps their products and reductions; pages
        # past the live ones (stale buffers) are computed and left out
        carry = _load_carry(m_scr, den_scr, acc_scr)
        for i in range(ppb):                      # table order: the fp32
            k, v = kv_load(*pages(i), i)          # carry is sequential
            k2, v2 = _head_rows(k, hg, lanes), _head_rows(v, hg, lanes)
            s = _dot_nt(q, k2)                    # (R, nrun * lanes)
            if nrun > 1:                          # each run's own tile
                s = jnp.concatenate(
                    [s[r * rg:(r + 1) * rg, r * lanes:(r + 1) * lanes]
                     for r in range(nrun)], axis=0)

            def pv_of(num, v2=v2):
                if nrun > 1:                      # back to block-diagonal
                    num = jnp.concatenate(
                        [jnp.where(run == r, num, 0) for r in range(nrun)],
                        axis=1)
                return _dot_nn(num.astype(jnp.int8), v2)

            new = _kv_block_update(carry, s, (j * ppb + i) * psize + kcol,
                                   length, own, lookup, pv_of, mi_ref,
                                   si_ref, inv_ref)
            carry = [jnp.where(i < count, a, b) for a, b in zip(new, carry)]
        _store_carry(carry, m_scr, den_scr, acc_scr)

    @pl.when(j == grid[3] - 1)
    def _epilogue():
        o_ref[0] = _finish(osc_ref, den_scr, acc_scr)


def _copy_pages(grid, step_pages, len_ref, btab_ref, k_hbm, v_hbm, kbuf,
                vbuf, sems, state):
    """Bring the live pages of this grid step (``grid`` = slot, step and
    their counts) into VMEM by DMA, whole (Hkv, P, D) blocks, after
    starting the next live step's copies (the next slot's when this one is
    done) into the other buffer.  ``state`` (SMEM) = (grid tag of the step
    whose pages are in flight, -1 if none; the buffer they land in), set
    to (-1, 0) at the grid's first step; a step no copy was started for
    (the grid's first live one, or one after an empty slot) starts its
    own.  Returns ``i -> (K, V)`` refs of the step's ``i``-th page."""
    b_i, j, nslots, nsteps = grid

    def page_copies(bb, step, buf, act):
        count, page = step_pages(bb, step, len_ref, btab_ref)
        for i in range(kbuf.shape[1]):
            @pl.when(i < count)
            def _copy():
                pg = page(i)
                act(pltpu.make_async_copy(k_hbm.at[pg], kbuf.at[buf, i],
                                          sems.at[0, buf]))
                act(pltpu.make_async_copy(v_hbm.at[pg], vbuf.at[buf, i],
                                          sems.at[1, buf]))

    buf = state[1]

    @pl.when(state[0] != b_i * nsteps + j)       # nothing in flight for us
    def _cold():
        page_copies(b_i, j, buf, lambda c: c.start())

    same = step_pages(b_i, j + 1, len_ref, btab_ref)[0] > 0
    nxt = jnp.minimum(b_i + 1, nslots - 1)
    has_next = same | ((b_i + 1 < nslots) & (len_ref[nxt] > 0))
    nb_, nj = jnp.where(same, b_i, b_i + 1), jnp.where(same, j + 1, 0)

    @pl.when(has_next)
    def _prefetch():
        page_copies(nb_, nj, 1 - buf, lambda c: c.start())
        state[0] = nb_ * nsteps + nj
        state[1] = 1 - buf

    @pl.when(jnp.logical_not(has_next))
    def _last():
        state[0] = -1

    page_copies(b_i, j, buf, lambda c: c.wait())
    return lambda i: (kbuf.at[buf, i], vbuf.at[buf, i])


def _paged_decode_kernel(psize, ppb, hg, manual, step_pages, len_ref,
                         btab_ref, q_ref, *rest):
    _paged_decode_body(psize, ppb, hg, manual, step_pages,
                       lambda kr, vr, _i: (kr[...], vr[...]),
                       len_ref, btab_ref, q_ref, *rest)


def _paged_call(kernel, geom, kv_maps, q_i8, k_pool, v_pool, block_tables,
                lengths, scales, M_idx, shift_idx, lut_q7, inv_s_logit,
                out_scale, interpret):
    """pallas_call shared by both paged wrappers: grid (slot, page run).
    With ``kv_maps`` each page of a run is a K and a V block operand the
    pipeliner brings; without, the pools stay in HBM and the kernel copies
    the pages (``_copy_pages``).  ``scales`` are extra SMEM operands."""
    b, hkv, g, d = q_i8.shape
    rows = hkv * g
    _, ppb, _, manual = geom
    slot_map = lambda bb, j, lens, btab: (bb, 0, 0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if manual:
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        kv_args = [k_pool, v_pool]
        copy_scratch = [
            pltpu.VMEM((2, ppb) + k_pool.shape[1:], k_pool.dtype),
            pltpu.VMEM((2, ppb) + v_pool.shape[1:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K, V) x buffer
            pltpu.SMEM((2,), jnp.int32),         # step in flight, buffer
        ]
    else:
        page = (1,) + k_pool.shape[1:]
        kv_specs = [pl.BlockSpec(page, m) for m in kv_maps for _ in (0, 1)]
        kv_args = [pool for _ in kv_maps for pool in (k_pool, v_pool)]
        copy_scratch = []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # lengths, block_tables
        grid=(b, pl.cdiv(block_tables.shape[1], ppb)),
        in_specs=[pl.BlockSpec((1, rows, d), slot_map)] + kv_specs + [
            pl.BlockSpec((2, LUT_SIZE // 2), lambda bb, j, lens, btab: (0, 0)),
        ] + [smem] * (len(scales) + 4),
        out_specs=pl.BlockSpec((1, rows, d), slot_map),
        scratch_shapes=copy_scratch + [
            pltpu.VMEM((rows, 128), jnp.int32),    # running max (col-bcast)
            pltpu.VMEM((rows, 128), jnp.float32),  # running denominator
            pltpu.VMEM((rows, d), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d), jnp.int8),
        # the kernel's own copies run one step ahead: the grid in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32).reshape(-1),
      jnp.asarray(block_tables, jnp.int32),
      q_i8.reshape(b, rows, d), *kv_args,
      jnp.asarray(lut_q7, jnp.int32).reshape(2, LUT_SIZE // 2),
      *[jnp.asarray(sc, jnp.float32).reshape(-1) for sc in scales],
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))
    return out.reshape(b, hkv, g, d)


def _page_geometry(k_pool, block_tables):
    """(page size, pages per grid step, heads per score row, whether the
    kernel copies pages itself) at these shapes.  Mosaic slices an HBM
    pool only where its rows fill whole 128-lane vregs; a narrower pool
    (head dim 64, or the int4 pool's packed rows) comes through block
    operands instead."""
    psize = k_pool.shape[2]
    ppb = pages_per_step(page_vmem_bytes(k_pool.shape, k_pool.dtype.itemsize),
                         block_tables.shape[1])
    return (psize, ppb, _heads_per_row(k_pool.shape[1], psize),
            k_pool.shape[3] % 128 == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_qattention(
    q_i8: jax.Array,          # int8 (B, Hkv, G, D) — one token/slot, grouped q
    k_pool: jax.Array,        # int8 (n_pages, Hkv, P, D) — global page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    lengths: jax.Array,       # int32 (B,): valid rows per slot
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, interpret: bool = False,
) -> jax.Array:
    """Paged continuous-batching decode attention: each grid step brings up
    to ``ppb`` live pages of one slot, across all its kv heads, through the
    slot's scalar-prefetched block-table row, and folds them into the carry
    in table order.  ``ppb`` comes from the page's bytes
    (``pages_per_step``).  Bit-exact vs
    ``ref.py::paged_decode_qattention_ref``."""
    geom = _page_geometry(k_pool, block_tables)
    psize, ppb = geom[:2]
    kv_maps = [paged_kv_index_map(psize, ppb, i) for i in range(ppb)]
    kernel = functools.partial(_paged_decode_kernel, *geom,
                               paged_step_pages(psize, ppb))
    return _paged_call(kernel, geom, kv_maps, q_i8, k_pool, v_pool,
                       block_tables, lengths, (), M_idx, shift_idx, lut_q7,
                       inv_s_logit, out_scale, interpret)


def _paged_decode_q4_kernel(psize, ppb, hg, manual, step_pages, len_ref,
                            btab_ref, q_ref, *rest):
    # int4-packed pool: a page arrives as (Hkv, P, D//2) planar nibbles and
    # is dequantized here in VMEM under its shared scale, looked up through
    # the table entry of the step's i-th block (past the table's end only
    # for dead pages, whose values are left out)
    nkv = 2 if manual else 2 * ppb
    ks_ref, vs_ref = rest[nkv + 1:nkv + 3]
    b_i, j = pl.program_id(0), pl.program_id(1)
    nb = btab_ref.shape[1]

    def load(kr, vr, i):
        pg = btab_ref[b_i, jnp.minimum(j * ppb + i, nb - 1)]
        return (dequant_kv_tile(kr[...], ks_ref[pg]),
                dequant_kv_tile(vr[...], vs_ref[pg]))

    _paged_decode_body(psize, ppb, hg, manual, step_pages, load, len_ref,
                       btab_ref, q_ref, *rest[:nkv + 1], *rest[nkv + 3:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_qattention_q4(
    q_i8: jax.Array,          # int8 (B, Hkv, G, D) — one token/slot, grouped q
    k_pool: jax.Array,        # uint8 (n_pages, Hkv, P, D//2) — packed pool
    v_pool: jax.Array,
    k_scale: jax.Array,       # fp32 (n_pages,): shared dequant scale per page
    v_scale: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    lengths: jax.Array,       # int32 (B,): valid rows per slot
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, interpret: bool = False,
) -> jax.Array:
    """Paged decode attention over the int4-PACKED page pool: the grid,
    page copies and datapath of ``paged_decode_qattention``, but each
    page streams at half the bytes (nibble-planar uint8 along the head dim)
    and is dequantized in VMEM under its shared fp32 scale — the
    fused-unpack idiom ``int4_matmul`` uses for weights; no dequantized KV
    view ever materializes in HBM.  Bit-exact vs
    ``ref.py::paged_decode_qattention_q4_ref``."""
    assert k_pool.shape[3] * 2 == q_i8.shape[3], (k_pool.shape, q_i8.shape)
    geom = _page_geometry(k_pool, block_tables)
    psize, ppb = geom[:2]
    kv_maps = [paged_kv_index_map(psize, ppb, i) for i in range(ppb)]
    kernel = functools.partial(_paged_decode_q4_kernel, *geom,
                               paged_step_pages(psize, ppb))
    return _paged_call(kernel, geom, kv_maps, q_i8, k_pool, v_pool,
                       block_tables, lengths, (k_scale, v_scale), M_idx,
                       shift_idx, lut_q7, inv_s_logit, out_scale, interpret)

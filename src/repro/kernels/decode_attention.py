"""Batched single-query decode attention over the int8 KV cache — the
continuous-batching serving kernel.

One grid step = (slot, kv head, KV block).  Every slot in the table decodes
at its own depth: the per-slot ``lengths`` vector rides as a scalar-prefetch
argument, so it is available both to the kernel body (per-slot masking) and
to the BlockSpec index maps, which CLAMP the KV block index to the slot's
last live block — grid steps past a slot's length re-address the block that
is already resident in VMEM, so the pipeliner issues no new DMA and short
slots genuinely pay no HBM traffic for the unused tail of their cache.

Per KV block the datapath is exactly the paper's Softmax Core —

    int8 q @ kᵀ -> int32 scores -> (max - s) -> fixed-point LUT index ->
    Q0.7 exp numerators -> int8 P @ int8 V on the MXU -> int32 partial

— with the same fp32 cross-block carry (running max rescale, denominator,
output accumulator) as ``flash_qattention``.  With a single KV block the
kernel degenerates to the paper's row-wise softmax and is bit-exact vs.
``kernels/ref.py::decode_qattention_ref``.

GQA: q heads arrive pre-grouped per kv head, (B, Hkv, G, D); K/V arrive in
the cache's native (B, Smax, Hkv, D) layout and each live KV block is
streamed from HBM exactly once, shared by the whole group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint as fxp
from repro.core.qsoftmax import LUT_SIZE, MASK_OFFSET
from repro.kernels.tiling import divisor_tile
from repro.kernels.quant_softmax import lut_lookup

NEG_INIT = -(1 << 30)


def _kv_load_i8(k_ref, v_ref, _b_i, _k_i):
    """Default KV tile loader: the cache already holds int8 codes."""
    return k_ref[0, 0], v_ref[0, 0]


def _kv_load_i8_seq_major(k_ref, v_ref, _b_i, _k_i):
    """KV tile loader for the contiguous (B, Smax, Hkv, D) cache."""
    return k_ref[0, :, 0], v_ref[0, :, 0]


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


def paged_kv_index_map(psize):
    """KV BlockSpec index map shared by BOTH paged decode kernels (int8 and
    int4-packed) over the head-major (n_pages, Hkv, P, D) pool: clamp the
    dead logical block to the last live one, THEN translate through the
    slot's scalar-prefetched block-table row.  One factory — not two
    copies — so the int8/q4 agreement is structural and ``pallas_lint`` can
    prove the returned page index stays inside the pool for every grid
    point."""
    def kv_map(bb, h, k, lens, btab):
        last_live = jnp.maximum((lens[bb] - 1) // psize, 0)
        return (btab[bb, jnp.minimum(k, last_live)], h, 0, 0)
    return kv_map


def dequant_kv_tile(w_u8, scale):
    """Fused in-VMEM dequant of one nibble-planar int4 KV tile.

    (rows, D//2) uint8 -> (rows, D) int8: sign-extend both nibble planes
    (same branch-free ``(x ^ 8) - 8`` as ``int4_matmul``/``core.packing``),
    concatenate along the head dim (planar layout), multiply by the page's
    shared fp32 scale, round, clip.  Bit-identical to
    ``packing.dequantize_kv_page`` — the oracles depend on it."""
    w = w_u8.astype(jnp.int32)
    lo = ((w & 15) ^ 8) - 8
    hi = (((w >> 4) & 15) ^ 8) - 8
    c4 = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    return jnp.clip(jnp.round(c4 * scale), -127, 127).astype(jnp.int8)


def _decode_body(g, bkv, kv_load, len_ref, q_ref, k_ref, v_ref, lut_ref,
                 mi_ref, si_ref, inv_ref, osc_ref, o_ref, m_scr, den_scr,
                 acc_scr):
    # shared datapath of every decode-attention variant: the int8 and the
    # int4-packed kernels differ ONLY in ``kv_load`` (identity load vs
    # fused nibble dequant), so the int8 path stays byte-identical and the
    # packed path inherits the oracle-exact accumulation order for free
    b_i = pl.program_id(0)
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b_i]                         # this slot's valid prefix
    live = (k_i * bkv) < length                   # dead blocks: no compute
                                                  # (and no DMA — index map
                                                  # re-addresses a resident
                                                  # block)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0]                           # (G, D) int8 — whole group
        k, v = kv_load(k_ref, v_ref, b_i, k_i)    # (bkv, D) int8 each
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)  # (G, bkv)
        kpos = k_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (g, bkv), 1)
        s = jnp.where(kpos < length, s, s - MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)
        m_old = m_scr[:, :1]
        m_new = jnp.maximum(m_old, lm)
        idx = jnp.clip(fxp.rescale(m_new - s, mi_ref[0], si_ref[0], out_bits=9),
                       0, LUT_SIZE - 1)
        num = lut_lookup(idx, lut_ref[...].astype(jnp.int32))      # Q0.7
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(num.astype(jnp.int8), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)  # (G, D)
        f = jnp.exp((m_old - m_new).astype(jnp.float32) * inv_ref[0])
        f = jnp.where(m_old == NEG_INIT, 0.0, f)
        den_scr[...] = den_scr[...] * f + den_b.astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * f + pv.astype(jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(k_i == nk - 1)
    def _epilogue():
        den = jnp.maximum(den_scr[:, :1], 1.0)
        o = acc_scr[...] / den * osc_ref[0]
        o_ref[0, 0] = jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


def _decode_kernel(g, bkv, len_ref, *rest):
    _decode_body(g, bkv, _kv_load_i8_seq_major, len_ref, *rest)


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
    kernel = functools.partial(_decode_kernel, g, bkv)
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


def _paged_decode_kernel(g, psize, len_ref, _btab_ref, *rest):
    # the block table feeds only the BlockSpec index maps (which pool page
    # backs this slot's k-th logical KV block); the body is exactly the
    # contiguous kernel's with block size = page size
    _decode_body(g, psize, _kv_load_i8, len_ref, *rest)


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
    """Paged continuous-batching decode attention: the KV BlockSpec index
    map follows the slot's scalar-prefetched block-table entry instead of a
    linear offset, so one grid step streams one *pool page* per kv head.
    The pool is head-major, so that (P, D) tile is the array's own minor
    pair and the block shape satisfies the TPU's (8, 128) tiling rule.

    Same clamping machinery as the contiguous kernel: grid steps past a
    slot's length re-address the slot's last live page — already resident
    in VMEM, so the pipeliner issues no DMA and short slots pay no HBM
    traffic for table entries beyond their chain.  One logical KV block ==
    one page, so the grid tiles exactly (no divisor fallback needed)."""
    b, hkv, g, d = q_i8.shape
    psize = k_pool.shape[2]
    nb = block_tables.shape[1]
    grid = (b, hkv, nb)
    kernel = functools.partial(_paged_decode_kernel, g, psize)
    kv_map = paged_kv_index_map(psize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # lengths, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bb, h, k, lens, btab: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, psize, d), kv_map),
            pl.BlockSpec((1, 1, psize, d), kv_map),
            pl.BlockSpec((LUT_SIZE,), lambda bb, h, k, lens, btab: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, h, k, lens, btab: (bb, h, 0, 0)),
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
      jnp.asarray(block_tables, jnp.int32),
      q_i8, k_pool, v_pool, lut_q7,
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))


def _paged_decode_q4_kernel(g, psize, len_ref, btab_ref, q_ref, k_ref, v_ref,
                            lut_ref, ks_ref, vs_ref, mi_ref, si_ref, inv_ref,
                            osc_ref, o_ref, m_scr, den_scr, acc_scr):
    # int4-packed pool: the KV tile arrives as (psize, D//2) planar nibbles;
    # dequant happens here in VMEM under the page's shared scale (looked up
    # through the block table — for a live step the clamped index map loaded
    # exactly page btab[b, k], so scale and payload always agree)
    def load(kr, vr, b_i, k_i):
        pg = btab_ref[b_i, k_i]
        return (dequant_kv_tile(kr[0, 0], ks_ref[pg]),
                dequant_kv_tile(vr[0, 0], vs_ref[pg]))

    _decode_body(g, psize, load, len_ref, q_ref, k_ref, v_ref, lut_ref,
                 mi_ref, si_ref, inv_ref, osc_ref, o_ref, m_scr, den_scr,
                 acc_scr)


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
    """Paged decode attention over the int4-PACKED page pool: identical
    grid/clamping/datapath to ``paged_decode_qattention``, but each page
    streams HBM->VMEM at half the bytes (nibble-planar uint8 along the head
    dim) and is dequantized inside the kernel body under its shared fp32
    scale — exactly the fused-unpack idiom ``int4_matmul`` uses for
    weights; no dequantized KV view ever materializes in HBM.  Bit-exact
    vs ``ref.py::paged_decode_qattention_q4_ref``."""
    b, hkv, g, d = q_i8.shape
    psize = k_pool.shape[2]
    dp = k_pool.shape[3]                          # D//2 packed bytes
    assert dp * 2 == d, (dp, d)
    nb = block_tables.shape[1]
    grid = (b, hkv, nb)
    kernel = functools.partial(_paged_decode_q4_kernel, g, psize)
    kv_map = paged_kv_index_map(psize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # lengths, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bb, h, k, lens, btab: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, psize, dp), kv_map),
            pl.BlockSpec((1, 1, psize, dp), kv_map),
            pl.BlockSpec((LUT_SIZE,), lambda bb, h, k, lens, btab: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),    # k page scales
            pl.BlockSpec(memory_space=pltpu.SMEM),    # v page scales
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, h, k, lens, btab: (bb, h, 0, 0)),
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
      jnp.asarray(block_tables, jnp.int32),
      q_i8, k_pool, v_pool, lut_q7,
      jnp.asarray(k_scale, jnp.float32).reshape(-1),
      jnp.asarray(v_scale, jnp.float32).reshape(-1),
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))

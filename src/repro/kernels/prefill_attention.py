"""Paged chunked-prefill flash attention — the block-table-walking prefill
kernel of the token-budget serving loop.

A prefill chunk runs ``S`` queries at absolute positions ``[pos0, pos0+S)``
for each slot, attending causally over the slot's WHOLE logical KV chain
``[0, pos0+S)``.  The chain lives in the global int8 page pool; earlier
chunks and shared prefix pages were written by previous forwards.  Before
this kernel the TPU path gathered the chain into a contiguous HBM view and
called the q7 flash family on it — a full copy of the slot's KV per chunk.
Here the KV BlockSpec index map walks the slot's scalar-prefetched
block-table row instead, so each pool page is streamed into VMEM exactly
once per (head, q-block) and no gathered view ever materializes.

Grid = (slot, q head, q block, logical KV block).  Dead-block clamping is
the same trick as ``paged_decode_qattention``, with the causal frontier of
the current q block standing in for the decode slot's length: KV blocks
past ``(pos0 + (q_i+1)*bq - 1) // P`` re-address the frontier page — already
resident in VMEM — so the pipeliner issues no DMA for them and a chunk at a
small ``pos0`` genuinely pays only for the pages that exist so far.

Per KV block the datapath is exactly the paper's Softmax Core (int8 QK^T ->
int32 scores -> LUT Q0.7 numerators -> int8 P@V on the MXU) with the fp32
cross-block carry of ``flash_qattention``; it is BIT-EXACT against the
block-online oracle ``kernels/ref.py::paged_prefill_qattention_ref`` for
any page count and any q-block size (see the oracle's docstring for why
block-level causal skipping is an exact identity).

GQA: queries arrive ungrouped (B, H, S, D); the KV index map divides the q
head by the group size, so each page is shared by the whole group without
duplicating KV in HBM.
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
    """Default int8 page load: the pool tile IS the code tile."""
    return k_ref[0, 0], v_ref[0, 0]


def prefill_kv_index_map(bq, psize, group):
    """KV BlockSpec index map shared by BOTH paged prefill kernels (int8
    and int4-packed): clamp dead logical blocks onto the q block's causal
    frontier, THEN translate through the block table — dead grid steps
    re-address a page already resident in VMEM, so the pipeliner skips the
    DMA.  Module-level so ``repro.analysis.pallas_lint`` can prove the
    returned page index stays inside the pool for every grid point (under
    the kernel's contract ``pos0 + sq <= nb * psize``)."""
    def kv_map(bb, hh, qi, ki, pos0s, btab):
        frontier = (pos0s[bb] + (qi + 1) * bq - 1) // psize
        return (btab[bb, jnp.minimum(ki, frontier)], hh // group, 0, 0)
    return kv_map


def _prefill_body(bq, psize, kv_load, pos0_ref, q_ref, k_ref, v_ref,
                  lut_ref, mi_ref, si_ref, inv_ref, osc_ref, o_ref,
                  m_scr, den_scr, acc_scr):
    b_i = pl.program_id(0)
    q_i = pl.program_id(2)
    k_i = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(k_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos0 = pos0_ref[b_i]
    # causal skip at q-block granularity: the block contributes only if its
    # first key position is <= the q block's last query position (skipped
    # blocks are exact identities of the online update — see the oracle)
    live = (k_i * psize) <= (pos0 + (q_i + 1) * bq - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0]                       # (bq, D) int8
        k, v = kv_load(k_ref, v_ref, b_i, k_i)   # (psize, D) int8 — one page
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)  # (bq,P)
        qpos = pos0 + q_i * bq + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, psize), 0)
        kpos = k_i * psize + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, psize), 1)
        s = jnp.where(kpos <= qpos, s, s - MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)
        m_old = m_scr[:, :1]
        m_new = jnp.maximum(m_old, lm)
        idx = jnp.clip(fxp.rescale(m_new - s, mi_ref[0], si_ref[0],
                                   out_bits=9), 0, LUT_SIZE - 1)
        num = lut_lookup(idx, lut_ref[...].astype(jnp.int32))      # Q0.7
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(num.astype(jnp.int8), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)  # (bq,D)
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


def _paged_prefill_kernel(bq, psize, pos0_ref, _btab_ref, *rest):
    # int8 pool: the block table is consumed only by the index map
    _prefill_body(bq, psize, _kv_load_i8, pos0_ref, *rest)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_prefill_qattention(
    q_i8: jax.Array,          # int8 (B, H, S, D) — chunk queries, ungrouped
    k_pool: jax.Array,        # int8 (n_pages, Hkv, P, D) — global page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    pos0: jax.Array,          # int32 (B,): page-aligned chunk start per slot
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, bq: int = 128, interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill attention over the paged int8 KV cache: int8
    (B, H, S, D) context for queries at positions [pos0, pos0+S) attending
    over each slot's whole block-table chain.  The chunk's own K/V rows
    must already be scattered into the pool (the chunk forward writes
    before it attends, so intra-chunk causality falls out of the mask)."""
    b, h, sq, d = q_i8.shape
    hkv, psize = k_pool.shape[1], k_pool.shape[2]
    group = h // hkv
    nb = block_tables.shape[1]
    bq = divisor_tile(bq, sq)
    grid = (b, h, sq // bq, nb)
    kernel = functools.partial(_paged_prefill_kernel, bq, psize)
    kv_map = prefill_kv_index_map(bq, psize, group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # pos0, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bb, hh, qi, ki, pos0s, btab: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, psize, d), kv_map),
            pl.BlockSpec((1, 1, psize, d), kv_map),
            pl.BlockSpec((LUT_SIZE,),
                         lambda bb, hh, qi, ki, pos0s, btab: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d),
            lambda bb, hh, qi, ki, pos0s, btab: (bb, hh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.int32),    # running max (col-broadcast)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos0, jnp.int32).reshape(-1),
      jnp.asarray(block_tables, jnp.int32),
      q_i8, k_pool, v_pool, lut_q7,
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))


def _paged_prefill_q4_kernel(bq, psize, pos0_ref, btab_ref, q_ref, k_ref,
                             v_ref, lut_ref, ks_ref, vs_ref, mi_ref, si_ref,
                             inv_ref, osc_ref, o_ref, m_scr, den_scr,
                             acc_scr):
    from repro.kernels.decode_attention import dequant_kv_tile

    # int4-packed pool: dequantize the half-width page tile in VMEM under
    # its shared scale (a live block's index map loaded exactly page
    # btab[b, k], so btab_ref[b_i, k_i] names the scale of the loaded tile)
    def load(kr, vr, b_i, k_i):
        pg = btab_ref[b_i, k_i]
        return (dequant_kv_tile(kr[0, 0], ks_ref[pg]),
                dequant_kv_tile(vr[0, 0], vs_ref[pg]))

    _prefill_body(bq, psize, load, pos0_ref, q_ref, k_ref, v_ref, lut_ref,
                  mi_ref, si_ref, inv_ref, osc_ref, o_ref, m_scr, den_scr,
                  acc_scr)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_prefill_qattention_q4(
    q_i8: jax.Array,          # int8 (B, H, S, D) — chunk queries, ungrouped
    k_pool: jax.Array,        # uint8 (n_pages, Hkv, P, D//2) — packed pool
    v_pool: jax.Array,
    k_scale: jax.Array,       # fp32 (n_pages,): shared dequant scale per page
    v_scale: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    pos0: jax.Array,          # int32 (B,): page-aligned chunk start per slot
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, bq: int = 128, interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill attention over the int4-PACKED page pool: the same
    grid/frontier clamping/datapath as ``paged_prefill_qattention``, with
    each pool page streamed HBM->VMEM at half the bytes and dequantized
    in-kernel under its shared fp32 page scale.  Bit-exact vs
    ``ref.py::paged_prefill_qattention_q4_ref``."""
    b, h, sq, d = q_i8.shape
    hkv, psize = k_pool.shape[1], k_pool.shape[2]
    dp = k_pool.shape[3]                          # D//2 packed bytes
    assert dp * 2 == d, (dp, d)
    group = h // hkv
    nb = block_tables.shape[1]
    bq = divisor_tile(bq, sq)
    grid = (b, h, sq // bq, nb)
    kernel = functools.partial(_paged_prefill_q4_kernel, bq, psize)
    kv_map = prefill_kv_index_map(bq, psize, group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # pos0, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bb, hh, qi, ki, pos0s, btab: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, psize, dp), kv_map),
            pl.BlockSpec((1, 1, psize, dp), kv_map),
            pl.BlockSpec((LUT_SIZE,),
                         lambda bb, hh, qi, ki, pos0s, btab: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),    # k page scales
            pl.BlockSpec(memory_space=pltpu.SMEM),    # v page scales
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d),
            lambda bb, hh, qi, ki, pos0s, btab: (bb, hh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.int32),    # running max (col-broadcast)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos0, jnp.int32).reshape(-1),
      jnp.asarray(block_tables, jnp.int32),
      q_i8, k_pool, v_pool, lut_q7,
      jnp.asarray(k_scale, jnp.float32).reshape(-1),
      jnp.asarray(v_scale, jnp.float32).reshape(-1),
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))

"""Online (flash) fully-quantized attention Pallas kernel.

The paper's Softmax Core assumes a whole row of QK^T in SRAM (seq 128).  At
32k-500k context that row no longer fits, so the LUT softmax is composed with
online softmax: per KV block the datapath is exactly the paper's —

    int8 QK^T -> int32 scores -> (max - s) -> fixed-point LUT index ->
    Q0.7 exp numerators -> int8 P @ int8 V on the MXU -> int32 partial

— and only the cross-block carried state (running max rescale factor,
denominator, output accumulator) is fp32, the same compromise FP8 flash
attention makes on GPUs (DESIGN.md §2).  With a single KV block the kernel
degenerates to the paper's row-wise softmax and is bit-exact vs. the oracle.

GQA is handled by the index_map (kv head = q head // group): no KV duplication
ever materializes in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint as fxp
from repro.core.qsoftmax import LUT_SIZE, MASK_OFFSET
from repro.kernels.quant_softmax import lut_lookup

NEG_INIT = -(1 << 30)


def _flash_kernel(bq, bkv, q_offset,
                  q_ref, k_ref, v_ref, lut_ref, mi_ref, si_ref, inv_ref,
                  osc_ref, o_ref, m_scr, den_scr, acc_scr):
    q_i = pl.program_id(1)
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal skip: block contributes only if its first key pos <= last q pos
    live = (k_i * bkv) <= (q_i * bq + bq - 1 + q_offset)

    @pl.when(live)
    def _block():
        q = q_ref[0]                      # (bq, D) int8
        k = k_ref[0]                      # (bkv, D) int8
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
        qpos = q_offset + q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(kpos <= qpos, s, s - MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
        m_old = m_scr[:, :1]
        m_new = jnp.maximum(m_old, lm)
        d = m_new - s
        idx = jnp.clip(fxp.rescale(d, mi_ref[0], si_ref[0], out_bits=9),
                       0, LUT_SIZE - 1)
        num = lut_lookup(idx, lut_ref[...].astype(jnp.int32))  # (bq,bkv) Q0.7
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(num.astype(jnp.int8), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
        # fp32 cross-block carry
        f = jnp.exp((m_old - m_new).astype(jnp.float32) * inv_ref[0])
        f = jnp.where(m_old == NEG_INIT, 0.0, f)
        den_scr[...] = den_scr[...] * f + den_b.astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * f + pv.astype(jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(k_i == nk - 1)
    def _epilogue():
        den = jnp.maximum(den_scr[:, :1], 1.0)
        o = acc_scr[...] / den * osc_ref[0]
        o_ref[0] = jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


def _decode_kernel(g, bkv, q_ref, k_ref, v_ref, lut_ref, mi_ref, si_ref,
                   inv_ref, osc_ref, len_ref, o_ref, m_scr, den_scr, acc_scr):
    k_i = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                  # (G, D) int8 — whole group
    k = k_ref[0]                                  # (bkv, D) int8
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)   # (G, bkv)
    kpos = k_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (g, bkv), 1)
    s = jnp.where(kpos < len_ref[0], s, s - MASK_OFFSET)
    lm = jnp.max(s, axis=-1, keepdims=True)
    m_old = m_scr[:, :1]
    m_new = jnp.maximum(m_old, lm)
    idx = jnp.clip(fxp.rescale(m_new - s, mi_ref[0], si_ref[0], out_bits=9),
                   0, LUT_SIZE - 1)
    num = lut_lookup(idx, lut_ref[...].astype(jnp.int32))
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
        o_ref[0] = jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def flash_qdecode(
    q_i8: jax.Array,      # int8 (Hkv, G, D) — one token, q heads grouped
    k_i8: jax.Array,      # int8 (Hkv, Smax, D) — the int8 KV cache
    v_i8: jax.Array,
    cache_len: jax.Array,  # int32 scalar: number of valid positions
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, bkv: int = 512, interpret: bool = False,
) -> jax.Array:
    """GQA decode kernel: each KV block is streamed from HBM exactly ONCE and
    shared by all `G` grouped query heads (the jnp.repeat / per-q-head
    streaming formulations pay `G`x the KV traffic — EXPERIMENTS.md §Perf C).
    Returns int8 (Hkv, G, D) on the attn_out grid."""
    hkv, g, d = q_i8.shape
    _, smax, _ = k_i8.shape
    bkv = min(bkv, smax)
    assert smax % bkv == 0
    grid = (hkv, smax // bkv)
    kernel = functools.partial(_decode_kernel, g, bkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h, k: (h, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, k: (h, k, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, k: (h, k, 0)),
            pl.BlockSpec((LUT_SIZE,), lambda h, k: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda h, k: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hkv, g, d), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.int32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_i8, k_i8, v_i8, lut_q7,
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1),
      jnp.asarray(cache_len, jnp.int32).reshape(1))


def flash_qattention_jax(
    q_i8: jax.Array,     # int8 (H, Sq, D)
    k_i8: jax.Array,     # int8 (Hkv, Skv, D)
    v_i8: jax.Array,     # int8 (Hkv, Skv, D)
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, q_offset=0, bkv: int = 512, window: int | None = None,
) -> jax.Array:
    """Pure-JAX mirror of the Pallas kernel (lax.scan over KV blocks; same
    integer per-block datapath, same fp32 carry).  This is what the dry-run
    lowers on the CPU backend so cost_analysis reflects the blocked algorithm,
    and what long-context serving uses off-TPU.  ``q_offset`` may be traced.
    ``window``: sliding-window attention size (mixtral)."""
    h, sq, d = q_i8.shape
    hkv, skv, _ = k_i8.shape
    group = h // hkv
    bkv = min(bkv, skv)
    assert skv % bkv == 0
    nkv = skv // bkv
    kb = k_i8.reshape(hkv, nkv, bkv, d).transpose(1, 0, 2, 3)
    vb = v_i8.reshape(hkv, nkv, bkv, d).transpose(1, 0, 2, 3)
    qpos = q_offset + jnp.arange(sq)[:, None]           # (Sq, 1)

    def step(carry, inp):
        m_old, den, acc = carry
        k_i, kblk, vblk = inp                           # (), (hkv,bkv,d) x2
        kg = jnp.repeat(kblk, group, axis=0)            # (h, bkv, d)
        vg = jnp.repeat(vblk, group, axis=0)
        s = jax.lax.dot_general(q_i8, kg, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.int32)
        kpos = k_i * bkv + jnp.arange(bkv)[None, :]     # (1, bkv)
        live = kpos <= qpos
        if window is not None:
            live &= kpos > (qpos - window)
        s = jnp.where(live[None], s, s - MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_old, lm)
        idx = jnp.clip(fxp.rescale(m_new - s, M_idx, shift_idx, out_bits=9),
                       0, LUT_SIZE - 1)
        num = jnp.take(lut_q7.astype(jnp.int32), idx)
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(num.astype(jnp.int8), vg,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.int32)
        f = jnp.exp((m_old - m_new).astype(jnp.float32) * inv_s_logit)
        f = jnp.where(m_old == NEG_INIT, 0.0, f)
        den = den * f + den_b.astype(jnp.float32)
        acc = acc * f + pv.astype(jnp.float32)
        return (m_new, den, acc), None

    m0 = jnp.full((h, sq, 1), NEG_INIT, jnp.int32)
    den0 = jnp.zeros((h, sq, 1), jnp.float32)
    acc0 = jnp.zeros((h, sq, d), jnp.float32)
    (m, den, acc), _ = jax.lax.scan(
        step, (m0, den0, acc0), (jnp.arange(nkv), kb, vb))
    o = acc / jnp.maximum(den, 1.0) * out_scale
    return jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "q_offset",
                                              "interpret"))
def flash_qattention(
    q_i8: jax.Array,     # int8 (H, Sq, D)
    k_i8: jax.Array,     # int8 (Hkv, Skv, D)
    v_i8: jax.Array,     # int8 (Hkv, Skv, D)
    M_idx: jax.Array,
    shift_idx: jax.Array,
    lut_q7: jax.Array,   # (256,) int32 Q0.7 table
    inv_s_logit: jax.Array,  # fp32: 1 / s_x  (real units per logit code)
    out_scale: jax.Array,    # fp32: s_o / s_v
    *,
    q_offset: int = 0,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    h, sq, d = q_i8.shape
    hkv, skv, _ = k_i8.shape
    group = h // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    grid = (h, sq // bq, skv // bkv)
    kernel = functools.partial(_flash_kernel, bq, bkv, q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((1, bkv, d), lambda hh, qi, ki, g=group: (hh // g, ki, 0)),
            pl.BlockSpec((1, bkv, d), lambda hh, qi, ki, g=group: (hh // g, ki, 0)),
            pl.BlockSpec((LUT_SIZE,), lambda hh, qi, ki: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.int32),    # running max (col-broadcast)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_i8, k_i8, v_i8, lut_q7,
      jnp.asarray(M_idx, jnp.int32).reshape(1),
      jnp.asarray(shift_idx, jnp.int32).reshape(1),
      jnp.asarray(inv_s_logit, jnp.float32).reshape(1),
      jnp.asarray(out_scale, jnp.float32).reshape(1))

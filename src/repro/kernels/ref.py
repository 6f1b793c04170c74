"""Pure-jnp oracles for every Pallas kernel.

Integer kernels must match these BIT-EXACTLY in interpret mode (tests assert
array equality, not allclose).  The flash attention kernel is block-online and
carries its cross-block state in fp32 (see DESIGN.md), so it is compared to
``qattention_ref`` with a small LSB tolerance — and bit-exactly when a single
KV block covers the row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp
from repro.core import packing
from repro.core import qlayernorm as qln
from repro.core import qsoftmax as qs


def int4_matmul_ref(
    x_i8: jax.Array,      # int8 (M, K)
    w_packed: jax.Array,  # uint8 (K//2, N), K-planar nibble packing
    bias_i32: jax.Array,  # int32 (N,)
    M_q: jax.Array,
    shift_q: jax.Array,
) -> jax.Array:
    """W4A8 integer matmul + bias + fixed-point requantize -> int8 (M, N)."""
    w = packing.unpack_int4_planar(w_packed, axis=0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_i8.astype(jnp.int8), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc = acc + bias_i32.astype(jnp.int32)
    return fxp.requantize(acc, M_q, shift_q, bits=8)


def int8_bitsplit_matmul_ref(
    x_i8: jax.Array,   # int8 (M, K)
    w_i8: jax.Array,   # int8 (K, N) full 8-bit codes
    bias_i32: jax.Array,
    M_q: jax.Array,
    shift_q: jax.Array,
) -> jax.Array:
    """8x8 product via two 8x4 passes + shift-add — the BIM Type-A identity.

    w = (w >> 4) * 16 + (w & 15): hi is signed int4, lo unsigned 4-bit.
    Mathematically identical to a direct int8 dot; computed the bit-split way
    so the kernel and oracle share the exact accumulation order budget.
    """
    w32 = w_i8.astype(jnp.int32)
    hi = (w32 >> 4).astype(jnp.int8)          # arithmetic shift: signed high nibble
    lo = (w32 & 15).astype(jnp.int8)          # unsigned low nibble, fits int8
    x = x_i8.astype(jnp.int8)
    dn = (((1,), (0,)), ((), ()))
    acc_hi = jax.lax.dot_general(x, hi, dn, preferred_element_type=jnp.int32)
    acc_lo = jax.lax.dot_general(x, lo, dn, preferred_element_type=jnp.int32)
    acc = (acc_hi << 4) + acc_lo + bias_i32.astype(jnp.int32)
    return fxp.requantize(acc, M_q, shift_q, bits=8)


def quant_softmax_ref(x_int, M_idx, shift_idx, lut, mask=None, axis=-1):
    return qs.quant_softmax(x_int, M_idx, shift_idx, lut, mask=mask, axis=axis)


def quant_layernorm_ref(x_int, p: qln.QLNParams, eps_codes: int = 1):
    return qln.quant_layernorm(x_int, p, eps_codes)


def qattention_ref(
    q_i8: jax.Array,    # int8 (H, Sq, D)
    k_i8: jax.Array,    # int8 (Hkv, Skv, D)
    v_i8: jax.Array,    # int8 (Hkv, Skv, D)
    M_idx: jax.Array,   # LUT index multiplier for (max - s) -> table steps
    shift_idx: jax.Array,
    lut: jax.Array,     # (256,) int32, Q0.7 codes (flash-compatible table)
    out_scale: jax.Array,  # fp32: s_o / s_v  (epilogue projection to out grid)
    causal: bool = True,
    q_offset: int = 0,  # absolute position of q row 0 (decode: cache length)
) -> jax.Array:
    """Row-wise fully-quantized attention oracle (paper-style, non-flash).

    Integer datapath: int8 QK^T -> int32 scores -> LUT numerators (Q0.7) ->
    integer P (codes sum ~127 per row) -> int32 P@V; the final division and
    output projection are the fp32 epilogue shared with the flash kernel.
    """
    h, sq, d = q_i8.shape
    hkv = k_i8.shape[0]
    group = h // hkv
    k_g = jnp.repeat(k_i8, group, axis=0)
    v_g = jnp.repeat(v_i8, group, axis=0)
    dn = (((2,), (2,)), ((0,), (0,)))
    s = jax.lax.dot_general(q_i8, k_g, dn, preferred_element_type=jnp.int32)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k_i8.shape[1])[None, :]
        s = jnp.where((kpos <= qpos)[None], s, s - qs.MASK_OFFSET)
    m = jnp.max(s, axis=-1, keepdims=True)
    dgap = (m - s).astype(jnp.int32)
    idx = jnp.clip(fxp.rescale(dgap, M_idx, shift_idx, out_bits=9), 0, 255)
    num = jnp.take(lut.astype(jnp.int32), idx)           # Q0.7 codes, <= 127
    den = jnp.maximum(jnp.sum(num, axis=-1, keepdims=True), 1)
    pv = jax.lax.dot_general(
        num.astype(jnp.int8), v_g.astype(jnp.int8),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )
    o = pv.astype(jnp.float32) / den.astype(jnp.float32)
    y = jnp.round(o * out_scale)
    return jnp.clip(y, -127, 127).astype(jnp.int8)


def decode_qattention_ref(
    q_i8: jax.Array,      # int8 (B, Hkv, G, D) — one query token per slot
    k_i8: jax.Array,      # int8 (B, Hkv, Smax, D) — int8 KV cache
    v_i8: jax.Array,
    lengths: jax.Array,   # int32 (B,): valid cache prefix per slot
    M_idx: jax.Array,
    shift_idx: jax.Array,
    lut: jax.Array,       # (256,) int32 Q0.7 codes
    out_scale: jax.Array,
) -> jax.Array:
    """Row-wise oracle for the continuous-batching decode kernel: per slot,
    paper-style LUT attention of one query over the first ``lengths[b]``
    cached positions.  int8 (B, Hkv, G, D) on the attn_out grid.

    Realized as ``qattention_ref`` with the query at absolute position
    ``lengths[b] - 1`` — the causal mask then admits exactly the valid
    prefix, so the masking semantics match the kernel bit-for-bit.
    """
    b, hkv, g, d = q_i8.shape

    def one(qb, kb, vb, ln):
        o = qattention_ref(qb.reshape(hkv * g, 1, d), kb, vb,
                           M_idx, shift_idx, lut, out_scale,
                           causal=True, q_offset=ln - 1)
        return o.reshape(hkv, g, d)

    return jax.vmap(one)(q_i8, k_i8, v_i8, lengths)


def paged_decode_qattention_ref(
    q_i8: jax.Array,          # int8 (B, Hkv, G, D) — one query token per slot
    k_pool: jax.Array,        # int8 (n_pages, Hkv, P, D) — global page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    lengths: jax.Array,       # int32 (B,): valid rows per slot
    M_idx: jax.Array,
    shift_idx: jax.Array,
    lut: jax.Array,           # (256,) int32 Q0.7 codes
    inv_s_logit: jax.Array,
    out_scale: jax.Array,
) -> jax.Array:
    """Block-online oracle for the PAGED decode kernel: per slot, one page
    per step gathered through the block table, with the kernel's exact
    accumulation order (int32 scores, Q0.7 LUT numerators, fp32 running
    max-rescale / denominator / output carry).  Because every operation and
    its order match ``_kv_block_update``, applied page by page in table
    order, the Pallas kernel is BIT-EXACT
    against this oracle for any page count — unlike the contiguous kernel,
    whose oracle is the row-wise ``decode_qattention_ref`` (exact only when
    one block covers the row)."""
    from repro.core.qsoftmax import LUT_SIZE

    b, hkv, g, d = q_i8.shape
    psize = k_pool.shape[2]
    nb = block_tables.shape[1]
    neg_init = -(1 << 30)
    m = jnp.full((b, hkv, g, 1), neg_init, jnp.int32)
    den = jnp.zeros((b, hkv, g, 1), jnp.float32)
    acc = jnp.zeros((b, hkv, g, d), jnp.float32)
    lut32 = lut.astype(jnp.int32)
    inv = jnp.asarray(inv_s_logit, jnp.float32)
    for k_i in range(nb):
        pg = block_tables[:, k_i]                          # (B,)
        kb = jnp.take(k_pool, pg, axis=0)            # (B,Hkv,P,D)
        vb = jnp.take(v_pool, pg, axis=0)
        s = jax.lax.dot_general(
            q_i8, kb, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.int32)              # (B,Hkv,G,P)
        kpos = k_i * psize + jnp.arange(psize, dtype=jnp.int32)
        s = jnp.where(kpos[None, None, None, :] < lengths[:, None, None, None],
                      s, s - qs.MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, lm)
        idx = jnp.clip(fxp.rescale(m_new - s, M_idx, shift_idx, out_bits=9),
                       0, LUT_SIZE - 1)
        num = jnp.take(lut32, idx)                         # Q0.7 numerators
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            num.astype(jnp.int8), vb, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.int32)              # (B,Hkv,G,D)
        f = jnp.exp((m - m_new).astype(jnp.float32) * inv)
        f = jnp.where(m == neg_init, 0.0, f)
        live = ((k_i * psize) < lengths)[:, None, None, None]
        den = jnp.where(live, den * f + den_b.astype(jnp.float32), den)
        acc = jnp.where(live, acc * f + pv.astype(jnp.float32), acc)
        m = jnp.where(live, m_new, m)
    den = jnp.maximum(den, 1.0)
    o = acc / den * out_scale
    return jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


def paged_prefill_qattention_ref(
    q_i8: jax.Array,          # int8 (B, H, Sq, D) — chunk queries, ungrouped
    k_pool: jax.Array,        # int8 (n_pages, Hkv, P, D) — global page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # int32 (B, max_blocks): slot -> pool pages
    pos0: jax.Array,          # int32 (B,): chunk start position per slot
    M_idx: jax.Array,
    shift_idx: jax.Array,
    lut: jax.Array,           # (256,) int32 Q0.7 codes
    inv_s_logit: jax.Array,
    out_scale: jax.Array,
) -> jax.Array:
    """Block-online oracle for the paged chunked-PREFILL kernel: queries at
    absolute positions [pos0[b], pos0[b]+Sq) attend causally over the
    slot's whole block-table chain, one pool page per step, with the
    kernel's exact accumulation order (int32 scores, Q0.7 LUT numerators,
    fp32 running max-rescale / denominator / output carry).

    The kernel additionally SKIPS blocks wholly past a q block's causal
    frontier; the oracle processes every block unconditionally.  These are
    bit-identical: a fully-masked block's scores sit MASK_OFFSET below any
    live score, so its row max never wins (``m_new == m_old`` exactly, and
    block 0 is live for every query, so ``m_old`` is never NEG_INIT after
    it), the rescale factor is ``exp(0) == 1.0`` (fp32-exact multiply), and
    its LUT indices clip to the table's terminal zero code — the update
    adds exact zeros.  That also makes the kernel's result independent of
    its q-block size, so the oracle needs no ``bq`` parameter."""
    from repro.core.qsoftmax import LUT_SIZE

    b, h, sq, d = q_i8.shape
    hkv, psize = k_pool.shape[1], k_pool.shape[2]
    group = h // hkv
    nb = block_tables.shape[1]
    neg_init = -(1 << 30)
    m = jnp.full((b, h, sq, 1), neg_init, jnp.int32)
    den = jnp.zeros((b, h, sq, 1), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    lut32 = lut.astype(jnp.int32)
    inv = jnp.asarray(inv_s_logit, jnp.float32)
    qpos = pos0[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]  # (B,Sq)
    for k_i in range(nb):
        pg = block_tables[:, k_i]                          # (B,)
        kb = jnp.take(k_pool, pg, axis=0)            # (B,Hkv,P,D)
        vb = jnp.take(v_pool, pg, axis=0)
        kb = jnp.repeat(kb, group, axis=1)                 # (B,H,P,D)
        vb = jnp.repeat(vb, group, axis=1)
        s = jax.lax.dot_general(
            q_i8, kb, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.int32)              # (B,H,Sq,P)
        kpos = k_i * psize + jnp.arange(psize, dtype=jnp.int32)
        live = kpos[None, None, None, :] <= qpos[:, None, :, None]
        s = jnp.where(live, s, s - qs.MASK_OFFSET)
        lm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, lm)
        idx = jnp.clip(fxp.rescale(m_new - s, M_idx, shift_idx, out_bits=9),
                       0, LUT_SIZE - 1)
        num = jnp.take(lut32, idx)                         # Q0.7 numerators
        den_b = jnp.sum(num, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            num.astype(jnp.int8), vb, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.int32)              # (B,H,Sq,D)
        f = jnp.exp((m - m_new).astype(jnp.float32) * inv)
        f = jnp.where(m == neg_init, 0.0, f)
        den = den * f + den_b.astype(jnp.float32)
        acc = acc * f + pv.astype(jnp.float32)
        m = m_new
    den = jnp.maximum(den, 1.0)
    o = acc / den * out_scale
    return jnp.clip(jnp.round(o), -127, 127).astype(jnp.int8)


def paged_decode_qattention_q4_ref(q_i8, k_pool_u8, v_pool_u8, k_scale,
                                   v_scale, block_tables, lengths, M_idx,
                                   shift_idx, lut, inv_s_logit, out_scale):
    """Oracle for the int4-packed paged decode kernel.

    Dequantizes the whole packed pool with the shared packing helpers
    (``clip(round(c4 * scale), -127, 127)`` — the exact formula the kernel
    fuses per tile) and delegates to the int8 block-online oracle.  This is
    an exact identity with the kernel's in-VMEM dequant: every page the
    kernel touches dequantizes to the same int8 codes this full view holds,
    and pages past a slot's length contribute nothing either way."""
    k_pool = packing.dequantize_kv_pool(k_pool_u8, k_scale)
    v_pool = packing.dequantize_kv_pool(v_pool_u8, v_scale)
    return paged_decode_qattention_ref(q_i8, k_pool, v_pool, block_tables,
                                       lengths, M_idx, shift_idx, lut,
                                       inv_s_logit, out_scale)


def paged_prefill_qattention_q4_ref(q_i8, k_pool_u8, v_pool_u8, k_scale,
                                    v_scale, block_tables, pos0, M_idx,
                                    shift_idx, lut, inv_s_logit, out_scale):
    """Oracle for the int4-packed paged prefill kernel (see the decode q4
    oracle for why whole-pool dequant + int8 oracle is bit-exact vs the
    kernel's fused per-tile dequant)."""
    k_pool = packing.dequantize_kv_pool(k_pool_u8, k_scale)
    v_pool = packing.dequantize_kv_pool(v_pool_u8, v_scale)
    return paged_prefill_qattention_ref(q_i8, k_pool, v_pool, block_tables,
                                        pos0, M_idx, shift_idx, lut,
                                        inv_s_logit, out_scale)


def make_exp_lut_q7():
    """Q0.7 exp table for the attention kernels (max code 127, fits int8)."""
    import numpy as np

    from repro.core.qsoftmax import LUT_DELTA, LUT_SIZE

    i = np.arange(LUT_SIZE, dtype=np.float64)
    vals = np.round(np.exp(-i * LUT_DELTA) * 127.0).astype(np.int32)
    vals[-1] = 0
    return vals

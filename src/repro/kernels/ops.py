"""Public jit'd wrappers over the Pallas kernels, with backend dispatch.

Backends (env ``REPRO_KERNELS`` or per-call override):
  * ``pallas``    — compiled Pallas (the TPU target).
  * ``interpret`` — Pallas interpret mode (CPU correctness; used by tests).
  * ``ref``       — pure-jnp oracles from ``kernels/ref.py`` (identical
                    integer semantics; what the dry-run lowers on CPU so
                    cost_analysis reflects the real algorithm, not the
                    interpreter).
  * ``auto``      — pallas on TPU, ref elsewhere (default).

Wrappers flatten leading dims, pad rows to tile multiples, and unpad —
model code never sees tiling constraints.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qlayernorm import QLNParams
from repro.core.qlinear import FoldedLinear
from repro.core.qsoftmax import MASK_OFFSET
from repro.kernels import ref as _ref
from repro.kernels import int4_matmul as _mm
from repro.kernels import quant_softmax as _sm
from repro.kernels import quant_layernorm as _ln
from repro.kernels import flash_qattention as _fa


def backend(override: Optional[str] = None) -> str:
    b = override or os.environ.get("REPRO_KERNELS", "auto")
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return b


def _pad_rows(x: jax.Array, mult: int):
    m = x.shape[0]
    pad = (-m) % mult
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


def linear_w4a8(x_i8: jax.Array, f: FoldedLinear, *, impl: Optional[str] = None):
    """y_i8 = requant(x_i8 @ W + b).  x: int8 (..., K) -> (..., N).

    ``f.w_bits`` picks the weight datapath: 4 = nibble-packed int4 codes,
    8 = full int8 codes through the BIM Type-A bit-split kernel."""
    assert f.w_bits in (4, 8), f.w_bits
    lead = x_i8.shape[:-1]
    k = x_i8.shape[-1]
    x2 = x_i8.reshape(-1, k)
    b = backend(impl)
    if b == "ref":
        ref_mm = (_ref.int4_matmul_ref if f.w_bits == 4 else
                  _ref.int8_bitsplit_matmul_ref)
        y = ref_mm(x2, f.w_packed, f.bias_i, f.M, f.shift)
        return y.reshape(*lead, -1)
    mm = _mm.int4_matmul if f.w_bits == 4 else _mm.int8_bitsplit_matmul
    x2p, m = _pad_rows(x2, 8)
    y = mm(x2p, f.w_packed, f.bias_i, f.M, f.shift,
           interpret=(b == "interpret"))
    return y[:m].reshape(*lead, -1)


def linear_w8a8_bitsplit(x_i8, w_i8, bias_i, M, shift, *, impl=None):
    """8x8 matmul realized as two 8x4 passes (BIM Type-A)."""
    lead = x_i8.shape[:-1]
    x2 = x_i8.reshape(-1, x_i8.shape[-1])
    b = backend(impl)
    if b == "ref":
        y = _ref.int8_bitsplit_matmul_ref(x2, w_i8, bias_i, M, shift)
        return y.reshape(*lead, -1)
    x2p, m = _pad_rows(x2, 8)
    y = _mm.int8_bitsplit_matmul(x2p, w_i8, bias_i, M, shift,
                                 interpret=(b == "interpret"))
    return y[:m].reshape(*lead, -1)


def softmax_q(x_int, M_idx, shift_idx, lut, mask=None, *, impl=None):
    """Quantized softmax over the last axis.  x_int: int32 codes."""
    b = backend(impl)
    if b == "ref":
        return _ref.quant_softmax_ref(x_int, M_idx, shift_idx, lut, mask=mask)
    if mask is not None:
        x_int = jnp.where(mask, x_int, x_int - MASK_OFFSET)
    lead = x_int.shape[:-1]
    s = x_int.shape[-1]
    x2 = x_int.reshape(-1, s)
    x2p, m = _pad_rows(x2, 8)
    y = _sm.quant_softmax(x2p, M_idx, shift_idx, lut, interpret=(b == "interpret"))
    return y[:m].reshape(*lead, s)


def layernorm_q(x_i8, p: QLNParams, *, eps_codes: int = 1, impl=None):
    b = backend(impl)
    if b == "ref":
        return _ref.quant_layernorm_ref(x_i8, p, eps_codes)
    lead = x_i8.shape[:-1]
    n = x_i8.shape[-1]
    x2 = x_i8.reshape(-1, n)
    x2p, m = _pad_rows(x2, 8)
    y = _ln.quant_layernorm(
        x2p, p.gamma_i, p.beta_aligned, p.M_out, p.shift_out,
        subtract_mean=p.subtract_mean, eps_codes=eps_codes,
        interpret=(b == "interpret"))
    return y[:m].reshape(*lead, n)


def decode_attention_q(
    q_i8, k_i8, v_i8, lengths, M_idx, shift_idx, lut_q7, inv_s_logit,
    out_scale, *, bkv: Optional[int] = None, impl=None,
):
    """Continuous-batching decode attention with per-slot length masking.

    (B, Hkv, G, D) grouped queries x (B, Smax, Hkv, D) cache-native int8 KV
    -> (B, Hkv, G, D) int8 context.  ref backend = row oracle (exact);
    pallas = the batched single-query flash kernel (skips KV blocks past
    each slot's length).  ``bkv=None`` (the default) lets
    ``kernels/autotune.py`` pick the KV tile from the roofline cost table
    for this shape; pass an int to pin it.
    """
    b = backend(impl)
    if b == "ref":
        return _ref.decode_qattention_ref(
            q_i8, k_i8.transpose(0, 2, 1, 3), v_i8.transpose(0, 2, 1, 3),
            lengths, M_idx, shift_idx, lut_q7, out_scale)
    if bkv is None:
        from repro.kernels import autotune
        bsz, smax, hkv, hd = k_i8.shape
        bkv = autotune.decode_bkv(smax, batch_slots=bsz, hkv=hkv, hd=hd)
    from repro.kernels.decode_attention import decode_qattention
    return decode_qattention(q_i8, k_i8, v_i8, lengths, M_idx, shift_idx,
                             lut_q7, inv_s_logit, out_scale, bkv=bkv,
                             interpret=(b == "interpret"))


def paged_decode_attention_q(
    q_i8, k_pool, v_pool, block_tables, lengths, M_idx, shift_idx, lut_q7,
    inv_s_logit, out_scale, *, impl=None,
):
    """Paged continuous-batching decode attention.

    (B, Hkv, G, D) grouped queries x (n_pages, Hkv, P, D) global int8 page
    pool, addressed per slot through a (B, max_blocks) block table ->
    (B, Hkv, G, D) int8 context.  ref backend = the block-online oracle
    (kernel-exact accumulation order); pallas = the scalar-prefetch paged
    kernel, bit-exact vs. the oracle for any page count.

    Under tensor parallelism the caller passes the rank-LOCAL head slice
    (q and pool Hkv axes both divided by tp) with the scalar-prefetched
    block table replicated — neither backend distinguishes a local slice
    from a small model, so no TP branch exists at this layer.
    """
    b = backend(impl)
    if b == "ref":
        return _ref.paged_decode_qattention_ref(
            q_i8, k_pool, v_pool, block_tables, lengths, M_idx, shift_idx,
            lut_q7, inv_s_logit, out_scale)
    from repro.kernels.decode_attention import paged_decode_qattention
    return paged_decode_qattention(
        q_i8, k_pool, v_pool, block_tables, lengths, M_idx, shift_idx,
        lut_q7, inv_s_logit, out_scale, interpret=(b == "interpret"))


def paged_decode_attention_q4(
    q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables, lengths,
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale, *, impl=None,
):
    """Paged decode attention over the int4-PACKED page pool.

    Same contract as ``paged_decode_attention_q`` but the pool leaves are
    (n_pages, Hkv, P, D//2) uint8 nibble-planar with (n_pages,) fp32 shared
    scales per page; the pallas backend fuses dequant into the kernel's
    inner loop (half the HBM bytes per page), the ref backend dequantizes
    the whole pool and runs the int8 oracle — bit-exact either way."""
    b = backend(impl)
    if b == "ref":
        return _ref.paged_decode_qattention_q4_ref(
            q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables,
            lengths, M_idx, shift_idx, lut_q7, inv_s_logit, out_scale)
    from repro.kernels.decode_attention import paged_decode_qattention_q4
    return paged_decode_qattention_q4(
        q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables, lengths,
        M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
        interpret=(b == "interpret"))


def paged_prefill_attention_q(
    q_i8, k_pool, v_pool, block_tables, pos0, M_idx, shift_idx, lut_q7,
    inv_s_logit, out_scale, *, bq: Optional[int] = None, impl=None,
):
    """Paged chunked-prefill attention.

    (B, H, S, D) chunk queries at positions [pos0, pos0+S) x (n_pages, Hkv,
    P, D) global int8 page pool, addressed per slot through a
    (B, max_blocks) block table -> (B, H, S, D) int8 context over each
    slot's whole mapped chain.  ref backend = the block-online oracle
    (kernel-exact accumulation order); pallas = the block-table-walking
    flash kernel, bit-exact vs. the oracle for any page count and q-block
    size.  The chunk's own K/V rows must already be scattered into the
    pool.  Under tensor parallelism the caller passes the rank-local head
    slice with the block table replicated (see paged_decode_attention_q);
    the chunk is the cross-rank work-division unit — every rank walks the
    same chunk over its own heads.  ``bq=None`` (the default) lets
    ``kernels/autotune.py`` pick the q-block from the roofline cost table
    for this shape (output is bq-independent, so tuning never moves bits);
    pass an int to pin it."""
    b = backend(impl)
    if b == "ref":
        return _ref.paged_prefill_qattention_ref(
            q_i8, k_pool, v_pool, block_tables, pos0, M_idx, shift_idx,
            lut_q7, inv_s_logit, out_scale)
    if bq is None:
        bq = _autotuned_bq(q_i8, k_pool, block_tables, kv_bits=8)
    from repro.kernels.prefill_attention import paged_prefill_qattention
    return paged_prefill_qattention(
        q_i8, k_pool, v_pool, block_tables, pos0, M_idx, shift_idx, lut_q7,
        inv_s_logit, out_scale, bq=bq, interpret=(b == "interpret"))


def _autotuned_bq(q_i8, k_pool, block_tables, *, kv_bits: int) -> int:
    from repro.kernels import autotune
    bsz, h, sq, hd = q_i8.shape
    return autotune.prefill_bq(
        sq, batch_slots=bsz, page_size=k_pool.shape[2],
        hkv=k_pool.shape[1], hd=hd, kv_bits=kv_bits,
        n_blocks=block_tables.shape[1], n_heads=h)


def paged_prefill_attention_q4(
    q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables, pos0,
    M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, bq: Optional[int] = None, impl=None,
):
    """Paged chunked-prefill attention over the int4-PACKED page pool (see
    ``paged_decode_attention_q4`` for the packed-pool contract and
    ``paged_prefill_attention_q`` for the prefill semantics)."""
    b = backend(impl)
    if b == "ref":
        return _ref.paged_prefill_qattention_q4_ref(
            q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables,
            pos0, M_idx, shift_idx, lut_q7, inv_s_logit, out_scale)
    if bq is None:
        bq = _autotuned_bq(q_i8, k_pool_u8, block_tables, kv_bits=4)
    from repro.kernels.prefill_attention import paged_prefill_qattention_q4
    return paged_prefill_qattention_q4(
        q_i8, k_pool_u8, v_pool_u8, k_scale, v_scale, block_tables, pos0,
        M_idx, shift_idx, lut_q7, inv_s_logit, out_scale, bq=bq,
        interpret=(b == "interpret"))


def attention_q(
    q_i8, k_i8, v_i8, M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
    *, causal: bool = True, q_offset: int = 0, impl=None,
):
    """Quantized attention, (B, H, Sq, D) x (B, Hkv, Skv, D) -> (B, H, Sq, D).

    ref backend = paper-style row softmax (exact); pallas = online flash.
    """
    b = backend(impl)
    bsz = q_i8.shape[0]

    if b == "ref":
        fn = lambda qq, kk, vv: _ref.qattention_ref(
            qq, kk, vv, M_idx, shift_idx, lut_q7, out_scale,
            causal=causal, q_offset=q_offset)
        return jax.vmap(fn)(q_i8, k_i8, v_i8)
    assert causal, "flash kernel is causal-only; BERT uses softmax_q"
    fn = lambda qq, kk, vv: _fa.flash_qattention(
        qq, kk, vv, M_idx, shift_idx, lut_q7, inv_s_logit, out_scale,
        q_offset=q_offset, interpret=(b == "interpret"))
    return jax.vmap(fn)(q_i8, k_i8, v_i8)

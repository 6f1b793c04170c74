"""Integer serving forward: prefill (+KV-cache build) and single-token decode
over the folded params (models/fold.py).  This is the paper's deployment
datapath: int8 activations end-to-end, packed-int4 weights, LUT softmax,
integer LN, int8 KV cache — with documented fp islands (RoPE rotation, MoE
router/combine, SSM inner recurrence).

Depth is a lax.scan over super-block reps; the KV/SSM cache rides as scan
xs/ys with a leading (n_reps,) axis per slot.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import fixedpoint as fxp
from repro.core.qlayernorm import QLNParams
from repro.core.qlinear import FoldedLinear
from repro.core.qsoftmax import MASK_OFFSET, make_exp_lut
from repro.analysis.boundary import kernel_boundary
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.flash_qattention import flash_qattention_jax
from repro.models import layers as L
from repro.models import mamba as Mb
from repro.models import xlstm as Xl
from repro.models.transformer import slot_kinds
from repro.models.fold import make_silu_lut  # noqa: F401  (re-export)


# --- primitive appliers -------------------------------------------------------

def _ln(x_i8, f, cfg):
    p = QLNParams(gamma_i=f["gamma_i"], beta_aligned=f["beta_al"],
                  M_out=f["M"], shift_out=f["sh"],
                  subtract_mean=(cfg.norm_type == "layernorm"))
    with jax.named_scope("norm"):
        return ops.layernorm_q(x_i8, p)


def _lin(x_i8, f, w_bits):
    # w_bits is plumbed explicitly from cfg.quant at every call site — a
    # module global would leak one config's width into another's trace when
    # two configs are traced in the same process
    fl = FoldedLinear(w_packed=f["w"], bias_i=f["b"], M=f["M"], shift=f["sh"],
                      w_bits=w_bits)
    return ops.linear_w4a8(x_i8, fl)


def _lin_wonly(x_f, f):
    """Weight-only int4 linear on fp activations (SSM islands)."""
    from repro.core import packing
    w = packing.unpack_int4_planar(f["w"], axis=0).astype(jnp.float32) * f["inv_s_w"]
    y = x_f @ w
    if "b" in f:
        y = y + f["b"]
    return y


def _rescale_i8(x_i8, f):
    y = fxp.rescale(x_i8.astype(jnp.int32), f["M"], f["sh"])
    return jnp.clip(y, -127, 127).astype(jnp.int8)


def _resid_add(x_i8, f_rescale, delta_i8):
    xr = fxp.rescale(x_i8.astype(jnp.int32), f_rescale["M"], f_rescale["sh"])
    return jnp.clip(xr + delta_i8.astype(jnp.int32), -127, 127).astype(jnp.int8)


def _lut8(x_i8, lut_i8):
    """int8 -> int8 elementwise via 256-entry LUT (one-hot select): the
    FFN activation, named ``act_lut`` on the device trace."""
    with jax.named_scope("act_lut"):
        idx = x_i8.astype(jnp.int32) + 128
        return jnp.take(lut_i8, idx).astype(jnp.int8)


def _rope_island(h_i8, inv_s_in, s_out, pos, cfg, qn=None):
    """dequant -> (qk_norm) -> rotate -> requant.  (B,S,H,D) int8."""
    hf = h_i8.astype(jnp.float32) * inv_s_in
    if qn is not None:
        hf = L.rmsnorm(hf, qn)
    if cfg.mrope_sections is not None:
        hf = L.apply_mrope(hf, pos, cfg.rope_theta, cfg.mrope_sections)
    elif not cfg.learned_pos:
        hf = L.apply_rope(hf, pos, cfg.rope_theta, cfg.partial_rotary)
    return jnp.clip(jnp.round(hf * s_out), -127, 127).astype(jnp.int8)


LUT_Q7 = None  # materialized lazily (module-level jnp constants break pallas)


def _lut_q7():
    return jnp.asarray(kref.make_exp_lut_q7())


def _lut_q8():
    return jnp.asarray(make_exp_lut())


# --- attention slot -----------------------------------------------------------

def _pos_vector(pos, b):
    """Normalize a scalar-or-(B,) position argument to a (B,) int32 vector.

    The decode graph is compiled once for the whole slot table; per-slot
    positions are what let requests at different depths share one step.
    """
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))


def _attn_rows_q8(qc, kc, vc, aq, cfg, mask):
    """Materialized row attention through the decode-identical integer
    datapath (q8 LUT softmax + M_pv requant).  ``mask`` is bool or None:
    (S,Skv) shared across the batch, or (B,S,Skv) per-slot (the verify
    forward's ragged causal frontiers).  Row r is bit-identical to a decode
    step at pos r over the same KV, which is what makes one-shot cached
    prefill + continuous decode reproduce lockstep replay token-for-token."""
    group = cfg.n_heads // cfg.n_kv_heads
    kg = jnp.repeat(kc, group, axis=2)
    vg = jnp.repeat(vc, group, axis=2)
    scores = jax.lax.dot_general(
        qc.transpose(0, 2, 1, 3), kg.transpose(0, 2, 3, 1),
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)                 # (B,H,S,S)
    if mask is not None:
        m = mask[:, None] if mask.ndim == 3 else mask[None, None]
        scores = jnp.where(m, scores, scores - MASK_OFFSET)
    probs = ops.softmax_q(scores, aq["M_idx"], aq["sh_idx"], _lut_q8())
    pv = jax.lax.dot_general(
        probs.astype(jnp.int8), vg.transpose(0, 2, 1, 3),
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)
    return jnp.clip(fxp.rescale(pv, aq["M_pv"], aq["sh_pv"]),
                    -127, 127).astype(jnp.int8)


def _qkv_rope(x_i8, f, cfg, pos):
    """Shared attention front half: LN -> q/k/v projections -> RoPE at
    ``pos`` ((B,S) absolute positions, or (B,S,3) for mrope).  Returns
    (qc (B,S,H,hd), kc/vc (B,S,Hkv,hd)) int8."""
    b, s, _ = x_i8.shape
    wb = cfg.quant.w_bits
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("qkv"):
        h = _ln(x_i8, f["ln1"], cfg)
        qc = _lin(h, f["wq"], wb).reshape(b, s, nh, hd)
        kc = _lin(h, f["wk"], wb).reshape(b, s, nkv, hd)
        vc = _lin(h, f["wv"], wb).reshape(b, s, nkv, hd)
        aq = f["attn_q"]
        qc = _rope_island(qc, aq["inv_s_qp"], aq["s_q"], pos, cfg,
                          aq.get("qn"))
        kc = _rope_island(kc, aq["inv_s_kp"], aq["s_k"], pos, cfg,
                          aq.get("kn"))
        return qc, kc, vc


def _flash_bkv(rows: int) -> int:
    """Largest KV block <= 512 that divides ``rows`` (flash_qattention_jax
    tiles the KV axis exactly)."""
    from repro.kernels.tiling import divisor_tile
    return divisor_tile(512, rows)


def _attn_prefill(x_i8, f, cfg, pos, row_exact: bool = False):
    b, s, d = x_i8.shape
    wb = cfg.quant.w_bits
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    qc, kc, vc = _qkv_rope(x_i8, f, cfg, pos)
    aq = f["attn_q"]
    with jax.named_scope("core"):
        if cfg.causal and row_exact:
            # decode-identical rows (see _attn_rows_q8), causal/SWA mask
            qpos = jnp.arange(s, dtype=jnp.int32)[:, None]
            kpos = jnp.arange(s, dtype=jnp.int32)[None, :]
            live = kpos <= qpos
            if cfg.sliding_window:
                live &= kpos > qpos - cfg.sliding_window
            ctx = _attn_rows_q8(qc, kc, vc, aq, cfg, live)
        elif cfg.causal:
            # blocked integer flash over KV (fp32 carry), per-batch vmap
            fn = lambda qq, kk, vv: flash_qattention_jax(
                qq, kk, vv, aq["M_idx"], aq["sh_idx"], _lut_q7(),
                aq["inv_s_logit"], aq["out_scale"],
                window=cfg.sliding_window, bkv=_flash_bkv(s))
            ctx = jax.vmap(fn)(qc.transpose(0, 2, 1, 3),
                               kc.transpose(0, 2, 1, 3),
                               vc.transpose(0, 2, 1, 3))  # (B,H,S,D) int8
        else:
            # bidirectional (BERT): paper-style row LUT softmax, materialized
            ctx = _attn_rows_q8(qc, kc, vc, aq, cfg, None)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    out = _lin(ctx, f["wo"], wb)
    return out, kc, vc


def _decode_qkv(x_i8, f, cfg, pos_vec):
    """Decode-step front half: per-slot (B,) positions broadcast to the
    single query row, then the shared LN/qkv/RoPE path."""
    b, s, _ = x_i8.shape
    pos = (jnp.broadcast_to(pos_vec[:, None, None], (b, s, 3))
           if cfg.mrope_sections is not None
           else jnp.broadcast_to(pos_vec[:, None], (b, s)))
    return _qkv_rope(x_i8, f, cfg, pos)


def _gqa_decode_jnp(qg, k_cache, v_cache, lengths, aq):
    """Masked single-query GQA over a (B, S*, Hkv, hd) int8 KV view WITHOUT
    materializing repeated KV: q heads grouped per kv head and batched into
    the dot.  (The jnp.repeat formulation multiplies KV-cache HBM traffic
    by `group` — 16x on llama3-405b; EXPERIMENTS.md §Perf it.3.)  Rows at
    ``>= lengths[b]`` are masked to LUT-zero, so the result is independent
    of the view's padding — the contiguous (Smax) and paged (gathered
    block-table) layouts produce bit-identical context."""
    srows = k_cache.shape[1]
    kt = k_cache.transpose(0, 2, 3, 1)                # (B,kv,hd,S*) int8
    scores = jax.lax.dot_general(
        qg, kt, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)             # (B,kv,g,S*)
    slot = jnp.arange(srows)
    valid = slot[None, :] < lengths[:, None]          # (B,S*)
    scores = jnp.where(valid[:, None, None, :], scores,
                       scores - MASK_OFFSET)
    probs = ops.softmax_q(scores, aq["M_idx"], aq["sh_idx"], _lut_q8())
    vt = v_cache.transpose(0, 2, 1, 3)                # (B,kv,S*,hd)
    pv = jax.lax.dot_general(
        probs.astype(jnp.int8), vt, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)             # (B,kv,g,hd)
    return jnp.clip(fxp.rescale(pv, aq["M_pv"], aq["sh_pv"]),
                    -127, 127).astype(jnp.int8)


def _attn_decode(x_i8, f, cfg, cache, pos_offset):
    """x (B,1,d); cache {'k','v'}: (B, Smax, Hkv, hd) int8.

    ``pos_offset`` may be a traced scalar (lockstep: all slots at the same
    depth) or a traced (B,) vector of per-slot positions (continuous
    batching: every slot decodes at its own depth within one compiled step).
    """
    b, s, d = x_i8.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    smax = cache["k"].shape[1]
    pos_vec = _pos_vector(pos_offset, b)                  # (B,) int32
    qc, kc, vc = _decode_qkv(x_i8, f, cfg, pos_vec)
    aq = f["attn_q"]
    # match the cache layout before the in-place update (avoids the SPMD
    # "involuntary full rematerialization" reshard of the whole cache)
    from repro.sharding import partition as Pt
    dpax = Pt.dp_axes_or_none()
    if dpax:
        kc = Pt.constrain(kc, dpax, None, None, "model")
        vc = Pt.constrain(vc, dpax, None, None, "model")
    # per-slot ring-buffer write for SWA; plain per-slot append otherwise
    widx = (pos_vec % smax) if cfg.sliding_window else pos_vec
    upd = jax.vmap(lambda c, u, w: jax.lax.dynamic_update_slice(c, u, (w, 0, 0)))
    with jax.named_scope("kv_write"):
        k_cache = upd(cache["k"], kc, widx)
        v_cache = upd(cache["v"], vc, widx)
    group = nh // nkv
    assert s == 1
    lengths = (jnp.minimum(pos_vec + 1, smax)    # valid ring prefix
               if cfg.sliding_window else pos_vec + 1)
    qg = qc.reshape(b, nkv, group, hd)                    # (B,kv,g,hd) int8
    with jax.named_scope("core"):
        if ops.backend() == "pallas":
            # TPU fast path: cache-native layout straight into the kernel
            # (no per-step transpose of the whole cache), one KV stream per
            # block shared by the whole q group, per-slot length masking.
            from repro.kernels.decode_attention import decode_qattention
            ctx = decode_qattention(
                qg, k_cache, v_cache, lengths,
                aq["M_idx"], aq["sh_idx"], _lut_q7(),
                aq["inv_s_logit"], aq["out_scale"])       # (B,kv,g,hd) int8
        else:
            ctx = _gqa_decode_jnp(qg, k_cache, v_cache, lengths, aq)
    ctx = ctx.reshape(b, nh, s, hd)                       # == (B,H,1,hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    out = _lin(ctx, f["wo"], cfg.quant.w_bits)
    return out, {"k": k_cache, "v": v_cache}


def _tp_slice(x, tp_axis, nloc, axis):
    """This rank's contiguous block of ``nloc`` heads along ``axis`` (only
    meaningful inside a shard_map over ``tp_axis``).  Q heads slice in the
    same contiguous blocks as KV heads, so GQA group structure — q head h
    reads kv head h // group — is preserved rank-locally."""
    r = jax.lax.axis_index(tp_axis)
    return jax.lax.dynamic_slice_in_dim(x, r * nloc, nloc, axis)


def _is_kv4(cslot) -> bool:
    """Packed int4 pool slots carry per-page scale leaves ('ks'/'vs')."""
    return isinstance(cslot, dict) and "ks" in cslot


def _seq_major(pages):
    """(B, nb, Hkv, P, hd) gathered head-major pages -> the slot-major
    contiguous (B, nb*P, Hkv, hd) view the jnp attention paths read."""
    b, nb, hkv, psize, hd = pages.shape
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, nb * psize, hkv, hd)


def _paged_view(pool, block_tables):
    """Gather a slot-major contiguous KV view out of the int8 pool (jnp
    fallback path only — the Pallas kernels walk the block table)."""
    return _seq_major(jnp.take(pool, block_tables, axis=0))


@kernel_boundary(why="gathered-view int4 dequant on the jnp fallback path; "
                     "the Pallas kernels do this per tile in VMEM")
def _dequant_paged_view(pool_u8, scales, block_tables):
    """Gather a slot-major contiguous KV view out of the PACKED pool and
    dequantize it (jnp fallback path only — the Pallas kernels dequantize
    per tile in VMEM and never build this).  (B, max_blocks*P, Hkv_loc, hd)
    int8.  Registered kernel boundary: the pool-scale float cast inside is
    the audited exemption on the ref backend."""
    from repro.core import packing
    pg = jnp.take(pool_u8, block_tables, axis=0)      # (B,nb,Hkv,P,hd/2) u8
    sc = jnp.take(scales, block_tables, axis=0)       # (B,nb)
    c4 = packing.unpack_int4_planar(pg, axis=-1)
    return _seq_major(
        packing.dequant_int4_codes(c4, sc[:, :, None, None, None]))


def _attn_decode_paged(x_i8, f, cfg, cache, pos_offset, block_tables,
                       tp_axis=None):
    """Paged decode step: x (B,1,d); cache {'k','v'}: (n_pages, Hkv, P, hd)
    int8 global page pool (head-major: one kv head's (P, hd) page tile is
    the block the Pallas kernels DMA); ``block_tables`` (B, max_blocks)
    int32 maps each slot's logical KV blocks onto pool pages.

    The K/V row for this token is scattered through the slot's block table
    (page = table[b, pos // P], row = pos % P); attention then reads the
    pool indirectly — block-table gather on the jnp path, scalar-prefetch
    page lookup inside the Pallas kernel.  Writes only ever land in pages
    the slot owns exclusively (refcount 1): shared prefix pages end strictly
    before the first written position (scheduler COW discipline).  Inactive
    slots (zeroed table rows) scatter into the reserved trash page 0.

    Under tensor parallelism (``tp_axis`` set, running inside a shard_map
    over that mesh axis) the pool's Hkv axis is the per-rank LOCAL slice;
    the block table stays replicated and page ids are global, so this same
    scatter/gather code addresses the rank's slice of the same pages every
    other rank touches.  Q/K/V are sliced to the rank's contiguous head
    block after the (replicated) projections, attention runs on local heads
    only, and the int8 context is all-gathered back to full heads before
    the output projection — a pure reassembly of independently-computed
    heads, so sharded decode is bit-identical to unsharded decode.
    """
    b, s, d = x_i8.shape
    assert not cfg.sliding_window, \
        "paged cache serves full-attention archs; SWA keeps the ring buffer"
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nkv_loc, psize = cache["k"].shape[1:3]                # Hkv / tp, P
    pos_vec = _pos_vector(pos_offset, b)                  # (B,) int32
    qc, kc, vc = _decode_qkv(x_i8, f, cfg, pos_vec)
    aq = f["attn_q"]
    assert s == 1
    group = nh // nkv
    kv4 = _is_kv4(cache)
    kc_full, vc_full = kc, vc                             # pre-TP-slice
    if tp_axis is not None:
        nh_loc = group * nkv_loc
        qc = _tp_slice(qc, tp_axis, nh_loc, 2)
        kc = _tp_slice(kc, tp_axis, nkv_loc, 2)
        vc = _tp_slice(vc, tp_axis, nkv_loc, 2)
    else:
        assert nkv_loc == nkv, (nkv_loc, nkv)
    with jax.named_scope("kv_write"):
        # write-through-table: one (Hkv_loc, hd) row per slot into its own page
        pg = jnp.take_along_axis(block_tables, (pos_vec // psize)[:, None],
                                 axis=1)[:, 0]                # (B,) page ids
        row = pos_vec % psize
        if kv4:
            from repro.core import packing
            # page scale from the FULL-head codes (rank-identical under TP — a
            # sliced amax would let ranks disagree on the shared scale): the
            # row opening a page (row == 0) sets a fresh scale from its own
            # codes, later rows reuse the page's existing scale so previously
            # written rows keep dequantizing to the same values
            ks_fresh = jax.vmap(packing.kv_page_scale)(kc_full[:, 0])   # (B,)
            vs_fresh = jax.vmap(packing.kv_page_scale)(vc_full[:, 0])
            ks_pg = jnp.where(row == 0, ks_fresh, cache["ks"][pg])
            vs_pg = jnp.where(row == 0, vs_fresh, cache["vs"][pg])
            kq = packing.quantize_kv_page(kc[:, 0], ks_pg[:, None, None])
            vq = packing.quantize_kv_page(vc[:, 0], vs_pg[:, None, None])
            k_pool = cache["k"].at[pg, :, row].set(kq)
            v_pool = cache["v"].at[pg, :, row].set(vq)
            npool = {"k": k_pool, "v": v_pool,
                     "ks": cache["ks"].at[pg].set(ks_pg),
                     "vs": cache["vs"].at[pg].set(vs_pg)}
        else:
            k_pool = cache["k"].at[pg, :, row].set(kc[:, 0])
            v_pool = cache["v"].at[pg, :, row].set(vc[:, 0])
            npool = {"k": k_pool, "v": v_pool}
    lengths = pos_vec + 1
    qg = qc.reshape(b, nkv_loc, group, hd)                # (B,kv,g,hd) int8
    with jax.named_scope("core"):
        if ops.backend() == "pallas":
            if kv4:
                from repro.kernels.decode_attention import \
                    paged_decode_qattention_q4
                ctx = paged_decode_qattention_q4(
                    qg, k_pool, v_pool, npool["ks"], npool["vs"], block_tables,
                    lengths, aq["M_idx"], aq["sh_idx"], _lut_q7(),
                    aq["inv_s_logit"], aq["out_scale"])       # (B,kv,g,hd) int8
            else:
                from repro.kernels.decode_attention import \
                    paged_decode_qattention
                ctx = paged_decode_qattention(
                    qg, k_pool, v_pool, block_tables, lengths,
                    aq["M_idx"], aq["sh_idx"], _lut_q7(),
                    aq["inv_s_logit"], aq["out_scale"])       # (B,kv,g,hd) int8
        else:
            # gathered per-slot view (B, max_blocks*P, Hkv_loc, hd); masking
            # makes the result bit-identical to the contiguous layout (int8)
            # resp. to the kernel's fused per-tile dequant (kv4)
            if kv4:
                k_view = _dequant_paged_view(k_pool, npool["ks"], block_tables)
                v_view = _dequant_paged_view(v_pool, npool["vs"], block_tables)
            else:
                k_view = _paged_view(k_pool, block_tables)
                v_view = _paged_view(v_pool, block_tables)
            ctx = _gqa_decode_jnp(qg, k_view, v_view, lengths, aq)
    if tp_axis is not None:
        # reassemble full heads (rank order == head order): int8 values
        # move, nothing is recomputed or re-rounded
        ctx = jax.lax.all_gather(ctx, tp_axis, axis=1, tiled=True)
    ctx = ctx.reshape(b, nh, s, hd)                       # == (B,H,1,hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    out = _lin(ctx, f["wo"], cfg.quant.w_bits)
    return out, npool


def _attn_prefill_paged(x_i8, f, cfg, cache, pos, block_tables, pos0,
                        row_exact, tp_axis=None):
    """Chunk prefill through the block table: queries at absolute positions
    [pos0, pos0+S) write their K/V rows into the slot's pages and attend
    over the slot's WHOLE mapped chain — shared prefix pages and earlier
    chunks (already resident in the pool) plus the rows written here.
    ``pos0`` is a page-aligned traced scalar, so one compiled shape per
    chunk size serves every chunk position: pos0 == 0 starts a prompt, a
    nonzero pos0 continues one (prefix-cache suffix or the next
    token-budget chunk).  Row-exact (q8) rows are bit-identical to decode
    steps at the same positions, so chunked prefill reproduces the one-shot
    and lockstep engines token for token on the ref/interpret backends; the
    pallas backend dispatches to the block-table-walking
    ``paged_prefill_qattention`` kernel, which streams prior-chunk KV
    straight from the page pool instead of gathering a contiguous view
    (self-consistent q7 family, like _attn_prefill).  Pad rows and
    trash-page rows sit at kpos > every real query and are causally
    masked.

    Under tensor parallelism (``tp_axis`` set) the chunk is the cross-rank
    work-division unit: every rank runs the SAME chunk on its own head
    slice of the pool (Hkv axis local, page ids global, block table
    replicated), then the int8 context all-gathers back to full heads for
    the output projection — same reassembly argument as
    ``_attn_decode_paged``, so sharded chunk prefill is bit-identical to
    unsharded on the row-exact path."""
    b, s, d = x_i8.shape
    wb = cfg.quant.w_bits
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nkv_loc, psize = cache["k"].shape[1:3]                # Hkv / tp, P
    qc, kc, vc = _qkv_rope(x_i8, f, cfg, pos)
    aq = f["attn_q"]
    kv4 = _is_kv4(cache)
    kc_full, vc_full = kc, vc                             # pre-TP-slice
    if tp_axis is not None:
        nh_loc = (nh // nkv) * nkv_loc
        qc = _tp_slice(qc, tp_axis, nh_loc, 2)
        kc = _tp_slice(kc, tp_axis, nkv_loc, 2)
        vc = _tp_slice(vc, tp_axis, nkv_loc, 2)
    else:
        assert nkv_loc == nkv, (nkv_loc, nkv)
    with jax.named_scope("kv_write"):
        nb_s = s // psize
        btab_slice = jax.lax.dynamic_slice_in_dim(block_tables, pos0 // psize,
                                                  nb_s, axis=1)
        ncache = _paged_prefill_write(cache, kc, vc, btab_slice,
                                      kc_full=kc_full, vc_full=vc_full)
    # kv4 drops the row-exact q8 identity claim by construction (a
    # decode-written page's scale comes from its first row, a prefill-
    # written page's from the whole page), so it always takes the q7
    # paged family — the quality-A/B contract, not the identity one
    with jax.named_scope("core"):
        if row_exact and not kv4:
            k_view = _paged_view(ncache["k"], block_tables)
            v_view = _paged_view(ncache["v"], block_tables)
            rows = k_view.shape[1]
            qpos = pos0 + jnp.arange(s, dtype=jnp.int32)[:, None]
            kpos = jnp.arange(rows, dtype=jnp.int32)[None, :]
            ctx = _attn_rows_q8(qc, k_view, v_view, aq, cfg, kpos <= qpos)
        elif kv4:
            pos0_vec = jnp.broadcast_to(
                jnp.asarray(pos0, jnp.int32).reshape(-1), (b,))
            ctx = ops.paged_prefill_attention_q4(
                qc.transpose(0, 2, 1, 3), ncache["k"], ncache["v"],
                ncache["ks"], ncache["vs"], block_tables, pos0_vec,
                aq["M_idx"], aq["sh_idx"], _lut_q7(),
                aq["inv_s_logit"], aq["out_scale"])           # (B,H,S,hd) int8
        else:
            pos0_vec = jnp.broadcast_to(
                jnp.asarray(pos0, jnp.int32).reshape(-1), (b,))
            ctx = ops.paged_prefill_attention_q(
                qc.transpose(0, 2, 1, 3), ncache["k"], ncache["v"],
                block_tables, pos0_vec, aq["M_idx"], aq["sh_idx"], _lut_q7(),
                aq["inv_s_logit"], aq["out_scale"])           # (B,H,S,hd) int8
    if tp_axis is not None:
        ctx = jax.lax.all_gather(ctx, tp_axis, axis=1, tiled=True)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    out = _lin(ctx, f["wo"], wb)
    return out, ncache


def _paged_prefill_write(cache, kc, vc, block_tables, kc_full=None,
                         vc_full=None):
    """Scatter a prefill chunk's K/V rows (B, S, Hkv, hd) into the page pool
    through the block table.  S must be a whole number of pages and every
    table entry a page the request owns — pad rows land inside owned pages
    (masked or overwritten by decode, same argument as the contiguous
    bucketed prefill).

    On a packed (kv4) pool each written page is quantized to int4 codes
    under ONE shared scale computed from the page's FULL-head codes
    (``kc_full``/``vc_full``, pre-TP-slice — every rank derives the same
    scale) and nibble-packed along hd; the scale leaves update in the same
    scatter so payload and scale always travel together."""
    psize = cache["k"].shape[2]
    b, s = kc.shape[0], kc.shape[1]
    nb = s // psize
    assert nb * psize == s and block_tables.shape[1] == nb, \
        (s, psize, block_tables.shape)
    # (B, S, Hkv, hd) rows -> (B, nb, Hkv, P, hd) head-major pages
    kr = kc.reshape(b, nb, psize, *kc.shape[2:]).swapaxes(2, 3)
    vr = vc.reshape(b, nb, psize, *vc.shape[2:]).swapaxes(2, 3)
    if _is_kv4(cache):
        from repro.core import packing
        kfr = (kc if kc_full is None else kc_full).reshape(
            b, nb, psize, *((kc if kc_full is None else kc_full).shape[2:]))
        vfr = (vc if vc_full is None else vc_full).reshape(
            b, nb, psize, *((vc if vc_full is None else vc_full).shape[2:]))
        ks = jax.vmap(jax.vmap(packing.kv_page_scale))(kfr)       # (b, nb)
        vs = jax.vmap(jax.vmap(packing.kv_page_scale))(vfr)
        kq = packing.quantize_kv_page(kr, ks[:, :, None, None, None])
        vq = packing.quantize_kv_page(vr, vs[:, :, None, None, None])
        return {"k": cache["k"].at[block_tables].set(kq),
                "v": cache["v"].at[block_tables].set(vq),
                "ks": cache["ks"].at[block_tables].set(ks),
                "vs": cache["vs"].at[block_tables].set(vs)}
    return {"k": cache["k"].at[block_tables].set(kr),
            "v": cache["v"].at[block_tables].set(vr)}


def _attn_verify_paged(x_i8, f, cfg, cache, pos_vec, block_tables, n_rows,
                       row_exact, tp_axis=None):
    """Speculative verify step: score S = k+1 candidate rows per slot in ONE
    forward — row 0 is the slot's committed last token, rows 1..k its draft
    proposals.  ``pos_vec`` (B,) is each slot's decode cursor (the absolute
    position of row 0); ``n_rows`` (B,) is each slot's REAL row count (1 +
    its ragged proposal length) — columns at or past it are padding.

    This is the chunk-prefill datapath driven decode-style: K/V rows
    scatter per (page, row) through the block table exactly like
    ``_attn_decode_paged`` (positions here are NOT page-aligned, so the
    whole-page prefill scatter does not apply), and attention reads the
    slot's whole mapped chain with per-slot causal frontiers.  Row i is
    bit-identical to a plain decode step at position ``pos_vec[b] + i``
    over the same KV prefix (row-exact backends), which is the property
    the greedy acceptance rule leans on: accepted tokens are exactly the
    tokens plain decode would have produced.

    Padding columns redirect their scatter to trash page 0; real columns
    past a slot's eventual accepted prefix leave garbage K/V rows ABOVE
    the slot's rolled-back cursor — positions the causal length masks hide
    until the cursor re-crosses them, at which point the owner rewrites
    them (same argument as chunk-prefill pad rows).  The allocator is
    untouched: pages were grown through ``Scheduler.grow`` before the
    forward and stay owned through rollback.

    Under tensor parallelism the same head-slice / all-gather scheme as
    ``_attn_decode_paged`` applies (replicated block tables and positions,
    rank-local Hkv slice, contexts reassembled before the output
    projection), so sharded verify stays bit-identical to unsharded."""
    b, s, d = x_i8.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nkv_loc, psize = cache["k"].shape[1:3]                # Hkv / tp, P
    assert not _is_kv4(cache), \
        "speculative verify serves the int8 pool (spec x kv4: ROADMAP)"
    assert cfg.mrope_sections is None, \
        "speculative verify does not serve mrope archs yet"
    positions = pos_vec[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    qc, kc, vc = _qkv_rope(x_i8, f, cfg, positions)
    aq = f["attn_q"]
    if tp_axis is not None:
        nh_loc = (nh // nkv) * nkv_loc
        qc = _tp_slice(qc, tp_axis, nh_loc, 2)
        kc = _tp_slice(kc, tp_axis, nkv_loc, 2)
        vc = _tp_slice(vc, tp_axis, nkv_loc, 2)
    else:
        assert nkv_loc == nkv, (nkv_loc, nkv)
    # decode-style per-row scatter, vectorized over the S columns; padding
    # columns land in the trash page (the block table would already map
    # beyond-chain positions there, but padding must not touch the last
    # real page's rows either)
    with jax.named_scope("kv_write"):
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < n_rows[:, None]
        pg = jnp.take_along_axis(block_tables, positions // psize, axis=1)
        pg = jnp.where(valid, pg, 0)
        row = jnp.where(valid, positions % psize, 0)
        ncache = {"k": cache["k"].at[pg, :, row].set(kc),
                  "v": cache["v"].at[pg, :, row].set(vc)}
    with jax.named_scope("core"):
        if row_exact:
            # gathered chain view + per-slot causal frontier: row i of slot b
            # attends rows [0, pos_vec[b] + i] — bit-identical to the decode
            # step at that position (see _attn_rows_q8)
            k_view = _paged_view(ncache["k"], block_tables)
            v_view = _paged_view(ncache["v"], block_tables)
            rows = k_view.shape[1]
            kpos = jnp.arange(rows, dtype=jnp.int32)[None, None, :]
            ctx = _attn_rows_q8(qc, k_view, v_view, aq, cfg,
                                kpos <= positions[:, :, None])
        else:
            # the paged prefill kernel IS the verifier: per-slot pos0 rides the
            # scalar-prefetch argument (its frontier math never needed a
            # page-aligned start), blocks past a chain are causally dead
            ctx = ops.paged_prefill_attention_q(
                qc.transpose(0, 2, 1, 3), ncache["k"], ncache["v"],
                block_tables, pos_vec, aq["M_idx"], aq["sh_idx"], _lut_q7(),
                aq["inv_s_logit"], aq["out_scale"])           # (B,H,S,hd) int8
    if tp_axis is not None:
        ctx = jax.lax.all_gather(ctx, tp_axis, axis=1, tiled=True)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    out = _lin(ctx, f["wo"], cfg.quant.w_bits)
    return out, ncache


# --- ffn slots ----------------------------------------------------------------

def _mlp_int(x_i8, f, cfg):
    wb = cfg.quant.w_bits
    h = _ln(x_i8, f["ln2"], cfg)
    if cfg.act == "swiglu":
        with jax.named_scope("up_gate"):
            g = _lin(h, f["wg"], wb)
            u = _lin(h, f["wu"], wb)
        g = _lut8(g, f["silu_lut"])
        prod = g.astype(jnp.int32) * u.astype(jnp.int32)       # int16-range
        hh = jnp.clip(fxp.rescale(prod, f["prod"]["M"], f["prod"]["sh"]),
                      -127, 127).astype(jnp.int8)
        with jax.named_scope("down"):
            return _lin(hh, f["wd"], wb)
    with jax.named_scope("up_gate"):
        g = _lin(h, f["w1"], wb)
    g = _lut8(g, f["gelu_lut"])
    g = _rescale_i8(g, f["gelu_rescale"])
    with jax.named_scope("down"):
        return _lin(g, f["w2"], wb)


def _moe_int(x_i8, f, cfg):
    """Integer experts; fp32 router + combine (documented islands)."""
    from repro.models.moe import capacity, topk_routing, scatter_dispatch, \
        gather_combine
    b, s, d = x_i8.shape
    t = b * s
    h = _ln(x_i8, f["ln2"], cfg).reshape(t, d)
    hf = h.astype(jnp.float32) * f["inv_s_mi"]
    gate_logits = hf @ f["router"]
    cap = capacity(t, cfg.n_experts, cfg.top_k)
    dest, gates, _ = topk_routing(gate_logits, cfg.top_k, cap)
    # integer dispatch: rows are moved, padding is 0 (on-grid), codes exact
    xe = scatter_dispatch(h, dest, cfg.n_experts, cap)
    xe = xe.reshape(cfg.n_experts, cap, d)
    fe = f["experts"]

    wb = cfg.quant.w_bits

    def expert_ffn(xe_i8, grp):
        def one(x1, wg, wu, wd):
            with jax.named_scope("up_gate"):
                g = _lin(x1, wg, wb)
                u = _lin(x1, wu, wb)
            g = _lut8(g, grp["silu_lut"])
            prod = g.astype(jnp.int32) * u.astype(jnp.int32)
            hh = jnp.clip(fxp.rescale(prod, grp["prod"]["M"], grp["prod"]["sh"]),
                          -127, 127).astype(jnp.int8)
            with jax.named_scope("down"):
                return _lin(hh, wd, wb)
        return jax.vmap(one)(xe_i8, grp["wg"], grp["wu"], grp["wd"])

    ye = expert_ffn(xe, fe)                                     # (E,C,d) int8
    yf = ye.astype(jnp.float32) * fe["inv_s_out"]
    yt = gather_combine(yf.reshape(cfg.n_experts * cap, d), dest, gates,
                        jnp.float32)
    if "shared" in f:
        sh = f["shared"]
        xs = jnp.broadcast_to(h[None], (cfg.n_shared_experts, t, d))
        ys = expert_ffn(xs, sh)
        yt = yt + jnp.sum(ys.astype(jnp.float32) * sh["inv_s_out"], 0)
    y_i8 = jnp.clip(jnp.round(yt * f["s_rm"]), -127, 127).astype(jnp.int8)
    return y_i8.reshape(b, s, d)


# --- ssm slots (weight-only int4, fp core — DESIGN.md §4) ----------------------

def _mamba_int(x_i8, f, cfg, state):
    b, s, d = x_i8.shape
    h = _ln(x_i8, f["ln1"], cfg)
    hf = h.astype(jnp.float32) * f["inv_s_in"]
    m = f["mx"]
    d_in, dt_rank = Mb.mamba_dims(cfg)
    n = cfg.mamba_d_state
    xz = _lin_wonly(hf, m["w_in"])
    xi, z = jnp.split(xz, 2, axis=-1)
    xc, conv_state = Mb._causal_conv(xi, m["conv_w"],
                                     None if state is None else state["conv"])
    xc = jax.nn.silu(xc + m["conv_b"])
    prm = _lin_wonly(xc, m["w_x"])
    dt_r, B_, C_ = jnp.split(prm, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(dt_r @ m["w_dt"] + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    if state is None:
        y = Mb._ssm_chunked(xc, dt, B_, C_, A, m["D"])
        new_state = None
    else:
        a = jnp.exp(dt[:, 0, :, None] * A)
        hstate = a * state["h"] + (dt[:, 0] * xc[:, 0])[..., None] * B_[:, 0, None, :]
        y = jnp.einsum("bdn,bn->bd", hstate, C_[:, 0])[:, None] + xc * m["D"]
        new_state = {"h": hstate, "conv": conv_state}
    y = y * jax.nn.silu(z)
    out = _lin_wonly(y, m["w_out"])
    out_i8 = jnp.clip(jnp.round(out * f["s_ra"]), -127, 127).astype(jnp.int8)
    return out_i8, new_state


def _xlstm_int(x_i8, f, cfg, state, kind):
    b, s, d = x_i8.shape
    h = _ln(x_i8, f["ln1"], cfg)
    hf = (h.astype(jnp.float32) * f["inv_s_in"]).astype(jnp.float32)
    m = f["mx"]

    def lw(name):
        return lambda xx: _lin_wonly(xx, m[name])

    if kind == "mlstm":
        nh = cfg.n_heads
        qh = Xl._heads(lw("wq")(hf), nh)
        kh = Xl._heads(lw("wk")(hf), nh) / math.sqrt(d // nh)
        vh = Xl._heads(lw("wv")(hf), nh)
        gi = hf @ m["w_ig"] + m["b_ig"]
        gf = hf @ m["w_fg"] + m["b_fg"]
        logf = jax.nn.log_sigmoid(gf)
        if state is None:
            y = Xl.mlstm_parallel(qh, kh, vh, gi, logf)
            new_state = None
        else:
            qt, kt, vt = qh[:, 0], kh[:, 0], vh[:, 0]
            git, logft = gi[:, 0], logf[:, 0]
            m_new = jnp.maximum(logft + state["m"], git)
            fdec = jnp.exp(logft + state["m"] - m_new)[..., None]
            iinc = jnp.exp(git - m_new)[..., None]
            C = fdec[..., None] * state["C"] + iinc[..., None] * (
                kt[..., :, None] * vt[..., None, :])
            nvec = fdec * state["n"] + iinc * kt
            num = jnp.einsum("bhe,bhef->bhf", qt, C)
            den = jnp.maximum(jnp.abs(jnp.sum(nvec * qt, -1)), jnp.exp(-m_new))
            y = (num / den[..., None])[:, None]
            new_state = {"C": C, "n": nvec, "m": m_new}
        y = y.reshape(b, s, d)
        og = jax.nn.sigmoid(hf @ m["w_og"] + m["b_og"])
        y = L.rmsnorm(y, m["ln_y"]) * og
        out = _lin_wonly(y, m["wo"])
    else:  # slstm — reuse the QAT fp implementation on dequantized input
        pol_off = cfg.quant
        params_fp = {k: (v if not (isinstance(v, dict)) else v) for k, v in m.items()}
        # reconstruct float weights from weight-only folds
        from repro.core import packing
        def unw(t):
            return (packing.unpack_int4_planar(t["w"], axis=0).astype(jnp.float32)
                    * t["inv_s_w"]) if isinstance(t, dict) and "w" in t else t
        pf = {k: unw(v) for k, v in m.items()}
        amax_stub = {kk: jnp.float32(0) for kk in Xl.SLSTM_SITES}
        import dataclasses as _dc
        cfg_fp = _dc.replace(cfg, quant=_dc.replace(cfg.quant, quantize_wa=False))
        y, _, new_state = Xl.slstm_qat(hf, pf, amax_stub, cfg_fp.quant, cfg_fp,
                                       state)
        out = y
    out_i8 = jnp.clip(jnp.round(out * f["s_ra"]), -127, 127).astype(jnp.int8)
    return out_i8, new_state


# --- whole-model serving forward -----------------------------------------------

def cache_rows(cfg: ModelConfig, max_len: int) -> int:
    """KV rows allocated per slot (the SWA ring buffer is window-sized).
    Single source of truth shared by init_cache and the serving engine's
    one-shot-prefill eligibility check."""
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Per-slot decode state, stacked (n_reps, ...)."""
    kinds = slot_kinds(cfg)
    smax = cache_rows(cfg, max_len)
    cache = {}
    for i, (mixer, _) in enumerate(kinds):
        if mixer == "attn":
            c = {"k": jnp.zeros((cfg.n_reps, batch, smax, cfg.n_kv_heads, cfg.hd),
                                jnp.int8),
                 "v": jnp.zeros((cfg.n_reps, batch, smax, cfg.n_kv_heads, cfg.hd),
                                jnp.int8)}
        elif mixer == "mamba":
            d_in, _ = Mb.mamba_dims(cfg)
            c = {"h": jnp.zeros((cfg.n_reps, batch, d_in, cfg.mamba_d_state),
                                jnp.float32),
                 "conv": jnp.zeros((cfg.n_reps, batch, cfg.mamba_d_conv - 1, d_in),
                                   jnp.float32)}
        elif mixer == "mlstm":
            dh = cfg.d_model // cfg.n_heads
            c = {"C": jnp.zeros((cfg.n_reps, batch, cfg.n_heads, dh, dh), jnp.float32),
                 "n": jnp.zeros((cfg.n_reps, batch, cfg.n_heads, dh), jnp.float32),
                 "m": jnp.zeros((cfg.n_reps, batch, cfg.n_heads), jnp.float32)}
        else:  # slstm
            dh = cfg.d_model // cfg.n_heads
            z = lambda: jnp.zeros((cfg.n_reps, batch, cfg.n_heads, dh), jnp.float32)
            c = {"c": z(), "n": z(), "h": z(), "m": z()}
        cache[f"slot{i}"] = c
    return cache


def paged_page_nbytes(cfg: ModelConfig, page_size: int,
                      kv_bits: int = 8) -> int:
    """HBM bytes one pool page occupies across every rep/slot leaf of the
    ``init_paged_cache`` pytree (K + V payload, plus the two fp32 page
    scales at ``kv_bits=4``).  The allocator carries this for pool-bytes
    accounting: at 4 bits a fixed byte budget holds ~2x the pages."""
    kinds = slot_kinds(cfg)
    hd = cfg.hd // 2 if kv_bits == 4 else cfg.hd
    per = 2 * page_size * cfg.n_kv_heads * hd       # k + v payload bytes
    if kv_bits == 4:
        per += 2 * 4                                 # ks + vs fp32 scales
    return cfg.n_reps * len(kinds) * per


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     kv_bits: int = 8) -> Dict:
    """Global paged KV pool, stacked (n_reps, n_pages, Hkv, P, hd) per attn
    slot (head-major pages: each kv head's (P, hd) tile is contiguous and
    is the block the Pallas kernels stream).  Pages are position-agnostic:
    a slot's (max_blocks,) block-table row, not the pool layout, decides
    which rows belong to which request.
    Only all-attention archs page (SSM/xLSTM state is O(1) per slot and
    SWA already ring-buffers to the window).

    ``kv_bits=4`` switches each slot to the PACKED layout: payload leaves
    become (n_reps, n_pages, Hkv, P, hd//2) uint8 (nibble-planar along hd —
    half the pool bytes) plus per-page fp32 shared-scale leaves 'ks'/'vs'
    of shape (n_reps, n_pages).  The trash-page scale initializes to the
    all-zero page's well-defined scale (1/7) so dead reads stay exact
    zeros."""
    kinds = slot_kinds(cfg)
    assert all(m == "attn" for m, _ in kinds) and not cfg.sliding_window, \
        "paged cache requires an all-attention, non-SWA arch"
    assert kv_bits in (8, 4), kv_bits
    if kv_bits == 4:
        from repro.core import packing
        assert cfg.hd % 2 == 0, cfg.hd
        shape = (cfg.n_reps, n_pages, cfg.n_kv_heads, page_size, cfg.hd // 2)
        sshape = (cfg.n_reps, n_pages)
        # NB: one jnp.full per leaf — sharing a single scale array across
        # leaves would alias buffers and break donate_argnums on the pool
        def s0():
            return jnp.full(sshape, 1.0 / packing.KV4_QMAX, jnp.float32)
        return {f"slot{i}": {"k": jnp.zeros(shape, jnp.uint8),
                             "v": jnp.zeros(shape, jnp.uint8),
                             "ks": s0(), "vs": s0()}
                for i in range(len(kinds))}
    shape = (cfg.n_reps, n_pages, cfg.n_kv_heads, page_size, cfg.hd)
    return {f"slot{i}": {"k": jnp.zeros(shape, jnp.int8),
                         "v": jnp.zeros(shape, jnp.int8)}
            for i in range(len(kinds))}


def _embed_int(cfg, folded, tokens):
    if cfg.frontend == "audio_codebooks":
        acc = sum(jnp.take(folded["embed"]["codebooks_i8"][ci], tokens[:, ci], 0
                           ).astype(jnp.int32) for ci in range(cfg.n_codebooks))
        return jnp.clip(acc, -127, 127).astype(jnp.int8)
    return jnp.take(folded["embed"]["tokens_i8"], tokens, axis=0)


def serve_forward(
    cfg: ModelConfig,
    folded: Dict,
    tokens: jax.Array,
    *,
    cache: Optional[Dict] = None,
    pos_offset: jax.Array | int = 0,
    mode: str = "prefill",            # prefill | decode | verify
    block_tables: Optional[jax.Array] = None,
    verify_rows: Optional[jax.Array] = None,
    extra_embeds_i8: Optional[jax.Array] = None,
    pos3: Optional[jax.Array] = None,
    tp_axis: Optional[str] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Integer forward.

    prefill without cache: tokens (B,S) -> logits (evaluation path, no cache
    update).  prefill WITH cache (attention archs only): additionally writes
    the per-layer K/V rows for positions [pos_offset, pos_offset+S) into the
    cache and returns it — the chunk-forward path of the continuous-batching
    engine (pos_offset == 0 and S == prompt length is the one-shot special
    case), computed through the decode-identical row datapath so a later
    chunk or decode continues bit-exactly.  decode: tokens (B,1) + cache ->
    (logits, new_cache); ``pos_offset`` is a scalar or a per-slot (B,)
    vector.

    verify (paged layouts only): tokens (B, k+1) holds each slot's last
    committed token followed by its draft proposals; ``pos_offset`` is the
    per-slot (B,) decode cursor and ``verify_rows`` (B,) each slot's real
    row count (ragged proposals ride one padded shape).  Every row's logits
    come back (B, k+1, vocab), each bit-identical (row-exact backends) to
    the decode step plain decode would have run at that position — the
    verifier half of speculative decoding (see ``_attn_verify_paged``).

    ``block_tables`` (B, max_blocks) int32 switches the cache layout to the
    paged pool (``init_paged_cache``): both the prefill scatter and the
    decode read/write then indirect through each slot's block-table row
    inside the depth scan instead of addressing a contiguous Smax stripe.

    ``tp_axis`` names the mesh axis of a tensor-parallel shard_map this
    forward is running inside (paged layouts only): the pool's Hkv axis is
    then the per-rank local slice, attention runs on the rank's contiguous
    head block, and contexts all-gather back to full heads before the
    output projection.  Everything outside attention is replicated compute
    on replicated data, so the returned logits are replicated and the whole
    sharded forward stays bit-identical to the unsharded one.
    """
    kinds = slot_kinds(cfg)
    assert tp_axis is None or block_tables is not None, \
        "tensor parallelism serves the paged cache layout only"
    assert mode != "verify" or (cache is not None
                                and block_tables is not None
                                and verify_rows is not None), \
        "verify mode needs a paged cache, block tables, and verify_rows"
    x = _embed_int(cfg, folded, tokens)
    if extra_embeds_i8 is not None:
        x = jnp.concatenate([extra_embeds_i8, x], axis=1)
    b, s = x.shape[0], x.shape[1]
    # prefill at a nonzero pos_offset continues an existing chain (the paged
    # suffix prefill after a prefix-cache hit); pos0 stays a traced scalar
    pos0 = jnp.asarray(pos_offset, jnp.int32).reshape(-1)[0]
    vpos = (_pos_vector(pos_offset, b) if mode == "verify" else None)  # (B,)
    if cfg.learned_pos:
        if mode == "decode":
            posrow = jnp.take(folded["embed"]["pos_i8"],
                              _pos_vector(pos_offset, b), axis=0)[:, None]
        elif mode == "verify":
            grid = vpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            posrow = jnp.take(folded["embed"]["pos_i8"], grid, axis=0)
        else:
            posrow = jax.lax.dynamic_slice_in_dim(
                folded["embed"]["pos_i8"], pos0, s, axis=0)[None]
        x = jnp.clip(x.astype(jnp.int32) + posrow.astype(jnp.int32),
                     -127, 127).astype(jnp.int8)
    if mode in ("decode", "verify"):
        pos = None
    else:
        pos = jnp.broadcast_to(pos0 + jnp.arange(s, dtype=jnp.int32), (b, s))
        if cfg.mrope_sections is not None:
            pos = pos3 if pos3 is not None else jnp.broadcast_to(
                pos[..., None], (*pos.shape, 3))

    def body(x_i8, f_rep, cache_rep):
        new_cache_rep = {}
        for i, (mixer, ffn) in enumerate(kinds):
            f = f_rep[f"slot{i}"]
            cslot = None if cache_rep is None else cache_rep[f"slot{i}"]
            if mixer == "attn":
                with jax.named_scope("attn"):
                    if mode == "decode":
                        out, nc = (
                            _attn_decode_paged(x_i8, f, cfg, cslot, pos_offset,
                                               block_tables, tp_axis=tp_axis)
                            if block_tables is not None
                            else _attn_decode(x_i8, f, cfg, cslot, pos_offset))
                    elif mode == "verify":
                        out, nc = _attn_verify_paged(
                            x_i8, f, cfg, cslot, vpos, block_tables,
                            verify_rows, row_exact=ops.backend() != "pallas",
                            tp_axis=tp_axis)
                    else:
                        # cached prefill matches the decode datapath per
                        # backend: row-exact q8 softmax mirrors the jnp
                        # decode (bit-exact continuation); on pallas both
                        # sides use the q7 flash family instead
                        # (self-consistent, not bit-identical)
                        row_exact = (cslot is not None
                                     and ops.backend() != "pallas")
                        if cslot is not None and block_tables is not None:
                            # chunk (or one-shot / suffix-only) prefill written
                            # and read through the block table
                            out, nc = _attn_prefill_paged(
                                x_i8, f, cfg, cslot, pos, block_tables, pos0,
                                row_exact, tp_axis=tp_axis)
                        else:
                            out, kc, vc = _attn_prefill(
                                x_i8, f, cfg, pos, row_exact=row_exact)
                            # one-shot prefill into the contiguous stripe
                            with jax.named_scope("kv_write"):
                                nc = (None if cslot is None else
                                      {"k": jax.lax.dynamic_update_slice(
                                          cslot["k"], kc, (0, 0, 0, 0)),
                                       "v": jax.lax.dynamic_update_slice(
                                          cslot["v"], vc, (0, 0, 0, 0))})
            elif mixer == "mamba":
                out, nc = _mamba_int(x_i8, f, cfg,
                                     cslot if mode == "decode" else None)
            else:
                out, nc = _xlstm_int(x_i8, f, cfg,
                                     cslot if mode == "decode" else None, mixer)
            new_cache_rep[f"slot{i}"] = nc if nc is not None else cslot
            x_i8 = _resid_add(x_i8, f["res_a"], out)
            if ffn == "dense":
                with jax.named_scope("mlp"):
                    out = _mlp_int(x_i8, f, cfg)
                x_i8 = _resid_add(x_i8, f["res_m"], out)
            elif ffn == "moe":
                with jax.named_scope("mlp"):
                    out = _moe_int(x_i8, f, cfg)
                x_i8 = _resid_add(x_i8, f["res_m"], out)
        x_i8 = _rescale_i8(x_i8, f_rep["block_out_rescale"])
        return x_i8, new_cache_rep

    def scan_body(carry, xs):
        if cache is None:
            f_rep = xs
            y, _ = body(carry, f_rep, None)
            return y, None
        f_rep, cache_rep = xs
        y, nc = body(carry, f_rep, cache_rep)
        return y, nc

    if cache is None:
        x, _ = jax.lax.scan(scan_body, x, folded["blocks"])
        new_cache = None
    else:
        x, new_cache = jax.lax.scan(scan_body, x, (folded["blocks"], cache))

    x = _ln(x, folded["final_norm"], cfg)
    head = folded["lm_head"]

    def head_apply(hw):
        from repro.core import packing
        w = (hw["w"] if cfg.quant.w_bits == 8 else
             packing.unpack_int4_planar(hw["w"], axis=0)).astype(jnp.int8)
        acc = jax.lax.dot_general(x, w, (((2,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * hw["inv_acc"]

    with jax.named_scope("lm_head"):
        logits = (jnp.stack([head_apply(jax.tree.map(lambda t: t[i], head))
                             for i in range(cfg.n_lm_heads)], axis=1)
                  if cfg.n_lm_heads > 1 and not cfg.tied_embeddings
                  else head_apply(head))
    return logits, new_cache

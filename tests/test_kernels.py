"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and bitwidth configurations.  Integer kernels must match
BIT-EXACTLY; the flash kernel matches the row oracle within 2 LSB and
bit-exactly in the single-block case."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixedpoint as fxp
from repro.core import packing as pk
from repro.core import qlayernorm as qln
from repro.core import qsoftmax as qs
from repro.kernels import ref as R
from repro.kernels import ops
from repro.kernels.int4_matmul import int4_matmul, int8_bitsplit_matmul
from repro.kernels.quant_softmax import quant_softmax as sm_kernel
from repro.kernels.quant_layernorm import quant_layernorm as ln_kernel
from repro.kernels.flash_qattention import flash_qattention, flash_qattention_jax
from paged_cases import PAGE_RUN_CASES, page_run_lengths

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n,bm,bn,bk2", [
    (8, 128, 128, 8, 128, 64),
    (32, 256, 128, 16, 64, 64),
    (64, 512, 384, 32, 128, 128),
    (128, 1024, 256, 128, 128, 256),
])
def test_int4_matmul_shapes(m, k, n, bm, bn, bk2):
    x = RNG.integers(-127, 128, (m, k)).astype(np.int8)
    codes = RNG.integers(-8, 8, (k, n)).astype(np.int8)
    wp = np.asarray(pk.pack_int4_planar(jnp.asarray(codes), axis=0))
    bias = RNG.integers(-5000, 5000, (n,)).astype(np.int32)
    M, sh = fxp.quantize_multiplier(0.00071)
    want = R.int4_matmul_ref(jnp.asarray(x), jnp.asarray(wp),
                             jnp.asarray(bias), jnp.int32(M), jnp.int32(sh))
    got = int4_matmul(jnp.asarray(x), jnp.asarray(wp), jnp.asarray(bias),
                      jnp.int32(M), jnp.int32(sh), bm=bm, bn=bn, bk2=bk2,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [(16, 128, 128), (32, 512, 256)])
def test_bitsplit_8x8_equals_direct(m, k, n):
    """BIM Type-A identity: two 8x4 passes + shift-add == direct int8 matmul."""
    x = RNG.integers(-127, 128, (m, k)).astype(np.int8)
    w = RNG.integers(-127, 128, (k, n)).astype(np.int8)
    bias = np.zeros(n, np.int32)
    M, sh = fxp.quantize_multiplier(0.0004)
    got = int8_bitsplit_matmul(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(bias), jnp.int32(M), jnp.int32(sh),
                               bm=16, bn=128, bk=128, interpret=True)
    want = R.int8_bitsplit_matmul_ref(jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray(bias), jnp.int32(M),
                                      jnp.int32(sh))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    acc = x.astype(np.int32) @ w.astype(np.int32)
    ideal = np.clip(np.round(acc * (M * 2.0 ** -sh)), -127, 127)
    assert np.max(np.abs(np.asarray(got) - ideal)) <= 1


@pytest.mark.parametrize("rows,cols,br", [(8, 64, 8), (32, 384, 8),
                                          (16, 1024, 4)])
def test_softmax_kernel_exact(rows, cols, br):
    lut = jnp.asarray(qs.make_exp_lut())
    s_x = 9.7
    M, sh = qs.index_multiplier(s_x)
    xi = np.round(RNG.normal(0, 3, (rows, cols)) * s_x).astype(np.int32)
    want = qs.quant_softmax(jnp.asarray(xi), jnp.int32(M), jnp.int32(sh), lut)
    got = sm_kernel(jnp.asarray(xi), jnp.int32(M), jnp.int32(sh), lut,
                    block_rows=br, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,n,sub", [(8, 128, True), (24, 256, False),
                                        (16, 1024, True)])
def test_layernorm_kernel_exact(rows, n, sub):
    g = (RNG.normal(0, 0.5, n) + 1).astype(np.float32)
    b = RNG.normal(0, 0.1, n).astype(np.float32) if sub else None
    p = qln.fold_layernorm(g, b, 31.0, subtract_mean=sub)
    xi = RNG.integers(-127, 128, (rows, n)).astype(np.int8)
    want = qln.quant_layernorm(jnp.asarray(xi), p)
    got = ln_kernel(jnp.asarray(xi), p.gamma_i, p.beta_aligned, p.M_out,
                    p.shift_out, subtract_mean=sub, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _attn_inputs(h, hkv, s, d, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.integers(-64, 65, (h, s, d)).astype(np.int8)
    k = rng.integers(-64, 65, (hkv, s, d)).astype(np.int8)
    v = rng.integers(-64, 65, (hkv, s, d)).astype(np.int8)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    return q, k, v, M, sh, s_logit


@pytest.mark.parametrize("h,hkv,s,d", [(2, 2, 128, 64), (4, 2, 256, 64),
                                       (4, 1, 128, 128)])
def test_flash_kernel_single_block_bit_exact(h, hkv, s, d):
    q, k, v, M, sh, s_logit = _attn_inputs(h, hkv, s, d)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    want = R.qattention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            jnp.int32(M), jnp.int32(sh), lut7,
                            jnp.float32(1.0), causal=True)
    got = flash_qattention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           jnp.int32(M), jnp.int32(sh), lut7,
                           jnp.float32(1.0 / s_logit), jnp.float32(1.0),
                           bq=s, bkv=s, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bq,bkv", [(64, 64), (128, 32), (32, 128)])
def test_flash_kernel_blocked_2lsb(bq, bkv):
    q, k, v, M, sh, s_logit = _attn_inputs(4, 2, 256, 64, seed=3)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    want = np.asarray(R.qattention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(M),
        jnp.int32(sh), lut7, jnp.float32(1.0), causal=True), np.int32)
    got = np.asarray(flash_qattention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(M),
        jnp.int32(sh), lut7, jnp.float32(1.0 / s_logit), jnp.float32(1.0),
        bq=bq, bkv=bkv, interpret=True), np.int32)
    assert np.max(np.abs(got - want)) <= 2


def test_flash_jax_matches_kernel_semantics():
    q, k, v, M, sh, s_logit = _attn_inputs(4, 2, 256, 64, seed=7)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    a = np.asarray(flash_qattention_jax(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(M),
        jnp.int32(sh), lut7, jnp.float32(1.0 / s_logit), jnp.float32(1.0),
        bkv=64), np.int32)
    b = np.asarray(flash_qattention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(M),
        jnp.int32(sh), lut7, jnp.float32(1.0 / s_logit), jnp.float32(1.0),
        bq=256, bkv=64, interpret=True), np.int32)
    assert np.max(np.abs(a - b)) <= 1


def test_flash_decode_offset():
    q, k, v, M, sh, s_logit = _attn_inputs(4, 2, 128, 64, seed=5)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    qd = q[:, :8]
    want = R.qattention_ref(jnp.asarray(qd), jnp.asarray(k), jnp.asarray(v),
                            jnp.int32(M), jnp.int32(sh), lut7,
                            jnp.float32(1.0), causal=True, q_offset=120)
    got = flash_qattention(jnp.asarray(qd), jnp.asarray(k), jnp.asarray(v),
                           jnp.int32(M), jnp.int32(sh), lut7,
                           jnp.float32(1.0 / s_logit), jnp.float32(1.0),
                           q_offset=120, bq=8, bkv=32, interpret=True)
    assert np.max(np.abs(np.asarray(got, np.int32)
                         - np.asarray(want, np.int32))) <= 1


def test_ops_dispatch_ref_vs_interpret():
    """ops wrappers give identical results through both backends."""
    from repro.core.qlinear import FoldedLinear
    x = RNG.integers(-127, 128, (5, 128)).astype(np.int8)  # odd rows -> pad
    codes = RNG.integers(-8, 8, (128, 64)).astype(np.int8)
    wp = pk.pack_int4_planar(jnp.asarray(codes), axis=0)
    M, sh = fxp.quantize_multiplier(0.001)
    f = FoldedLinear(wp, jnp.zeros(64, jnp.int32), jnp.int32(M), jnp.int32(sh), 4)
    a = ops.linear_w4a8(jnp.asarray(x), f, impl="ref")
    b = ops.linear_w4a8(jnp.asarray(x), f, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_w8_linear_runs_bitsplit_kernel():
    """w_bits=8 linears go through the bit-split kernel off the ref backend
    (no silent jnp fallback), bit-exact against its oracle."""
    from repro.core.qlinear import FoldedLinear
    x = RNG.integers(-127, 128, (5, 128)).astype(np.int8)
    w = RNG.integers(-128, 128, (128, 64)).astype(np.int8)
    M, sh = fxp.quantize_multiplier(0.0005)
    f = FoldedLinear(jnp.asarray(w), jnp.arange(64, dtype=jnp.int32),
                     jnp.int32(M), jnp.int32(sh), 8)
    a = ops.linear_w4a8(jnp.asarray(x), f, impl="ref")
    jaxpr = jax.make_jaxpr(
        lambda xx: ops.linear_w4a8(xx, f, impl="interpret"))(jnp.asarray(x))
    assert "pallas_call" in str(jaxpr)
    b = ops.linear_w4a8(jnp.asarray(x), f, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bkv", [128, 32, 64, 48])  # 48: falls back to a
def test_decode_attention_batched_per_slot_lengths(bkv):  # divisor of smax
    """Continuous-batching decode kernel: every slot masked to its own cache
    prefix, bit-exact vs. the row oracle (single block) and within 1 LSB
    when the fp32 carry spans blocks."""
    from repro.kernels.decode_attention import decode_qattention

    b, hkv, g, smax, d = 4, 2, 4, 128, 64
    rng = np.random.default_rng(19)
    q = rng.integers(-64, 65, (b, hkv, g, d)).astype(np.int8)
    # kernel takes the cache-NATIVE layout (B, Smax, Hkv, D)
    k = rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8)
    v = rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8)
    lengths = np.asarray([1, 37, 64, 128], np.int32)   # mixed-depth slots
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    got = np.asarray(decode_qattention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lengths),
        jnp.int32(M), jnp.int32(sh), lut7, jnp.float32(1.0 / s_logit),
        jnp.float32(1.0), bkv=bkv, interpret=True), np.int32)
    want = np.asarray(R.decode_qattention_ref(
        jnp.asarray(q), jnp.asarray(k.transpose(0, 2, 1, 3)),
        jnp.asarray(v.transpose(0, 2, 1, 3)), jnp.asarray(lengths),
        jnp.int32(M), jnp.int32(sh), lut7, jnp.float32(1.0)), np.int32)
    if bkv >= smax:
        np.testing.assert_array_equal(got, want)
    else:
        assert np.max(np.abs(got - want)) <= 1


def test_decode_attention_ops_dispatch():
    """ops.decode_attention_q: ref and interpret backends agree (single
    block -> bit-exact)."""
    b, hkv, g, smax, d = 2, 1, 2, 64, 32
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.integers(-64, 65, (b, hkv, g, d)).astype(np.int8))
    k = jnp.asarray(rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8))
    v = jnp.asarray(rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8))
    lengths = jnp.asarray([5, 64], jnp.int32)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    a = ops.decode_attention_q(q, k, v, lengths, jnp.int32(M), jnp.int32(sh),
                               lut7, jnp.float32(1.0 / s_logit),
                               jnp.float32(1.0), impl="ref")
    c = ops.decode_attention_q(q, k, v, lengths, jnp.int32(M), jnp.int32(sh),
                               lut7, jnp.float32(1.0 / s_logit),
                               jnp.float32(1.0), impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def _seq_view(pool, btab):
    """Gathered contiguous (B, nb*P, Hkv, D) view of head-major pool pages."""
    pages = np.asarray(pool)[np.asarray(btab)]          # (B, nb, Hkv, P, D)
    b, nb, hkv, psize, d = pages.shape
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, nb * psize, hkv, d)


def _paged_inputs(b, hkv, g, d, psize, n_pages, nb, lengths, seed=31):
    """Random pool + per-slot block tables (distinct pages per slot; unused
    table entries alias the trash page 0, like the serving engine's)."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-64, 65, (b, hkv, g, d)).astype(np.int8)
    kp = rng.integers(-64, 65, (n_pages, hkv, psize, d)).astype(np.int8)
    vp = rng.integers(-64, 65, (n_pages, hkv, psize, d)).astype(np.int8)
    perm = iter(rng.permutation(np.arange(1, n_pages)))
    btab = np.zeros((b, nb), np.int32)
    for bb, ln in enumerate(lengths):
        for i in range(-(-int(ln) // psize)):
            btab[bb, i] = next(perm)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    return q, kp, vp, btab, M, sh, s_logit


@pytest.mark.parametrize("psize,lengths,geom", [
    pytest.param(64, [1, 37, 64], None, id="64-lengths0"),   # one page a slot
    pytest.param(16, [1, 23, 48], None, id="16-lengths1"),   # cross-page carry
    pytest.param(8, [5, 17, 40], None, id="8-lengths2"),
] + PAGE_RUN_CASES)
def test_paged_decode_attention_bit_exact_vs_oracle(psize, lengths, geom):
    """The paged decode kernel follows per-slot block tables (non-monotone
    page order) and must be BIT-EXACT against the block-online oracle
    (same accumulation order) for any page count, including runs of pages
    cut short by a slot's length and slots of very different lengths."""
    from repro.kernels.decode_attention import paged_decode_qattention

    if geom is None:
        b, (hkv, g, d), nb = 3, (2, 4, 64), 64 // psize
    else:
        hkv, g, d, psize, nb = geom
        lengths = page_run_lengths(geom, lengths)
        b = len(lengths)
    n_pages = b * nb + 1
    q, kp, vp, btab, M, sh, s_logit = _paged_inputs(
        b, hkv, g, d, psize, n_pages, nb, lengths)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(btab), jnp.asarray(lengths, jnp.int32),
            jnp.int32(M), jnp.int32(sh), lut7,
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    got = np.asarray(paged_decode_qattention(*args, interpret=True), np.int32)
    want = np.asarray(R.paged_decode_qattention_ref(*args), np.int32)
    np.testing.assert_array_equal(got, want)
    # the gathered contiguous view through the row oracle stays within the
    # documented 1-LSB flash tolerance (exact when one page covers a slot);
    # an empty slot has no row to attend to, so the row oracle skips it
    kv, vv = _seq_view(kp, btab), _seq_view(vp, btab)
    row = np.asarray(R.decode_qattention_ref(
        jnp.asarray(q), jnp.asarray(kv.transpose(0, 2, 1, 3)),
        jnp.asarray(vv.transpose(0, 2, 1, 3)),
        jnp.asarray(lengths, jnp.int32), jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0)), np.int32)
    live = np.asarray(lengths) > 0
    assert np.max(np.abs(got - row)[live]) <= (0 if psize >= 64 else 1)


def test_paged_decode_attention_ops_dispatch():
    """ops.paged_decode_attention_q: ref (block-online oracle) and
    interpret (Pallas kernel) backends agree bit-for-bit."""
    b, hkv, g, d, psize, nb = 2, 1, 2, 32, 8, 4
    q, kp, vp, btab, M, sh, s_logit = _paged_inputs(
        b, hkv, g, d, psize, b * nb + 1, nb, [9, 32], seed=5)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(btab), jnp.asarray([9, 32], jnp.int32),
            jnp.int32(M), jnp.int32(sh), lut7,
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    a = ops.paged_decode_attention_q(*args, impl="ref")
    c = ops.paged_decode_attention_q(*args, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_paged_matches_contiguous_decode_kernel():
    """With an identity block table (page i == rows [i*P, (i+1)*P)), the
    paged kernel must reproduce the contiguous decode kernel bit-for-bit
    when block size == page size (the same blocks in the same order)."""
    from repro.kernels.decode_attention import (decode_qattention,
                                                paged_decode_qattention)

    b, hkv, g, d, smax, psize = 2, 2, 4, 64, 64, 16
    rng = np.random.default_rng(13)
    q = rng.integers(-64, 65, (b, hkv, g, d)).astype(np.int8)
    k = rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8)
    v = rng.integers(-64, 65, (b, smax, hkv, d)).astype(np.int8)
    lengths = np.asarray([29, 64], np.int32)
    nb = smax // psize
    # pool = per-slot stripes split into pages; table = identity chains
    kp = k.reshape(b * nb, psize, hkv, d).transpose(0, 2, 1, 3)
    vp = v.reshape(b * nb, psize, hkv, d).transpose(0, 2, 1, 3)
    btab = np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    cont = np.asarray(decode_qattention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lengths), jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0 / s_logit), jnp.float32(1.0), bkv=psize,
        interpret=True))
    paged = np.asarray(paged_decode_qattention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(btab),
        jnp.asarray(lengths), jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0 / s_logit), jnp.float32(1.0), interpret=True))
    np.testing.assert_array_equal(paged, cont)


@pytest.mark.parametrize("bkv,cache_len", [(128, 128), (32, 100), (64, 37)])
def test_flash_qdecode_matches_row_oracle(bkv, cache_len):
    """GQA decode kernel (KV streamed once per block for the whole q group)
    vs the row oracle evaluated at the cache tip."""
    from repro.kernels.flash_qattention import flash_qdecode

    hkv, g, smax, d = 2, 4, 128, 64
    rng = np.random.default_rng(11)
    q = rng.integers(-64, 65, (hkv, g, d)).astype(np.int8)
    k = rng.integers(-64, 65, (hkv, smax, d)).astype(np.int8)
    v = rng.integers(-64, 65, (hkv, smax, d)).astype(np.int8)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    got = np.asarray(flash_qdecode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.int32(cache_len), jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0 / s_logit), jnp.float32(1.0), bkv=bkv,
        interpret=True), np.int32)
    # oracle: per-q-head attention over the first cache_len positions,
    # realized as causal with q at position cache_len - 1
    # ref expects (H, Sq, D) with kv (Hkv, S, D); group mapping h -> h // g
    q_flat = q.reshape(hkv * g, 1, d)
    want = np.asarray(R.qattention_ref(
        jnp.asarray(q_flat), jnp.asarray(k), jnp.asarray(v),
        jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0), causal=True, q_offset=cache_len - 1), np.int32)
    want = want.reshape(hkv, g, d)
    assert np.max(np.abs(got - want)) <= 1


def _paged_prefill_inputs(b, h, hkv, d, psize, nb, sq, pos0, seed=37):
    """Random pool + per-slot chains covering [0, pos0[b]+sq) rows; unused
    table entries alias the trash page 0, like the serving engine's."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-64, 65, (b, h, sq, d)).astype(np.int8)
    n_pages = b * nb + 1
    kp = rng.integers(-64, 65, (n_pages, hkv, psize, d)).astype(np.int8)
    vp = rng.integers(-64, 65, (n_pages, hkv, psize, d)).astype(np.int8)
    perm = iter(rng.permutation(np.arange(1, n_pages)))
    btab = np.zeros((b, nb), np.int32)
    for bb in range(b):
        for i in range(-(-(int(pos0[bb]) + sq) // psize)):
            btab[bb, i] = next(perm)
    s_logit = 1.0 / (0.05 * np.sqrt(d))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    return q, kp, vp, btab, M, sh, s_logit


@pytest.mark.parametrize("psize,sq,pos0,bq", [
    (16, 16, [0, 16], 16),        # single q block, chunk continuation
    (8, 16, [8, 32], 8),          # multi q block, mid-chain chunks
    (8, 24, [0, 16], 4),          # bq < page, ragged grid mix
    (16, 32, [16, 48], 32),       # chunk spanning several pages
])
def test_paged_prefill_kernel_bit_exact_vs_oracle(psize, sq, pos0, bq):
    """The paged chunked-prefill kernel walks per-slot block tables through
    the scalar-prefetch index map (causal-frontier dead-block clamping) and
    must be BIT-EXACT against the block-online oracle for any page count,
    chunk position, and q-block size."""
    from repro.kernels.prefill_attention import paged_prefill_qattention

    b, h, hkv, d = 2, 4, 2, 64
    pos0 = np.asarray(pos0, np.int32)
    nb = -(-(int(pos0.max()) + sq) // psize) + 1     # + one dead tail block
    q, kp, vp, btab, M, sh, s_logit = _paged_prefill_inputs(
        b, h, hkv, d, psize, nb, sq, pos0)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(btab), jnp.asarray(pos0),
            jnp.int32(M), jnp.int32(sh), lut7,
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    got = np.asarray(paged_prefill_qattention(*args, bq=bq, interpret=True),
                     np.int32)
    want = np.asarray(R.paged_prefill_qattention_ref(*args), np.int32)
    np.testing.assert_array_equal(got, want)
    # sanity vs the row oracle on the gathered contiguous view: within the
    # documented flash tolerance (fp32 cross-block carry)
    kv, vv = _seq_view(kp, btab), _seq_view(vp, btab)
    for bb in range(b):
        row = np.asarray(R.qattention_ref(
            jnp.asarray(q[bb]),
            jnp.asarray(kv[bb].transpose(1, 0, 2)),
            jnp.asarray(vv[bb].transpose(1, 0, 2)),
            jnp.int32(M), jnp.int32(sh), lut7, jnp.float32(1.0),
            causal=True, q_offset=int(pos0[bb])), np.int32)
        assert np.max(np.abs(got[bb] - row)) <= 2


def test_paged_prefill_ops_dispatch():
    """ops.paged_prefill_attention_q: ref (block-online oracle) and
    interpret (Pallas kernel) backends agree bit-for-bit."""
    b, h, hkv, d, psize, sq = 2, 2, 1, 32, 8, 16
    pos0 = np.asarray([0, 8], np.int32)
    nb = -(-(int(pos0.max()) + sq) // psize)
    q, kp, vp, btab, M, sh, s_logit = _paged_prefill_inputs(
        b, h, hkv, d, psize, nb, sq, pos0, seed=3)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(btab), jnp.asarray(pos0),
            jnp.int32(M), jnp.int32(sh), lut7,
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    a = ops.paged_prefill_attention_q(*args, impl="ref")
    c = ops.paged_prefill_attention_q(*args, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

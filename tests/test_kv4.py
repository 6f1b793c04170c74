"""int4-packed KV pool: packing round-trips + fused-dequant kernel oracles.

The packed-pool contract has three parties that must agree bit-for-bit:
``core/packing.py`` (quantize/dequantize formulas), the Pallas kernels'
in-VMEM ``dequant_kv_tile``, and the ``kernels/ref.py`` q4 oracles (whole
pool dequant + int8 block-online oracle).  These tests pin all three to
each other on CPU interpret mode, over multi-page chains and ragged
lengths — the same harness shapes as the int8 paged kernel tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixedpoint as fxp
from repro.core import packing
from repro.core import qsoftmax as qs
from repro.kernels import ops
from repro.kernels import ref as R
from paged_cases import PAGE_RUN_CASES, page_run_lengths


# --- packing round-trips -------------------------------------------------------

@pytest.mark.parametrize("shape,axis", [
    ((6,), 0),
    ((3, 8), 1),          # odd leading dim
    ((5, 7, 4), 2),       # odd dims everywhere but the packed axis
    ((2, 3, 5, 16), -1),  # pool-like rank
])
def test_planar_pack_round_trip(shape, axis):
    rng = np.random.default_rng(0)
    codes = rng.integers(-8, 8, shape).astype(np.int8)
    packed = packing.pack_int4_planar(jnp.asarray(codes), axis=axis)
    assert packed.shape[axis % len(shape)] == shape[axis % len(shape)] // 2
    assert packed.dtype == jnp.uint8
    back = packing.unpack_int4_planar(packed, axis=axis)
    np.testing.assert_array_equal(np.asarray(back), codes)


@pytest.mark.parametrize("shape,axis", [((4,), 0), ((3, 6), 1), ((8, 3), 0)])
def test_pair_pack_round_trip(shape, axis):
    rng = np.random.default_rng(1)
    codes = rng.integers(-8, 8, shape).astype(np.int8)
    back = packing.unpack_int4(packing.pack_int4(jnp.asarray(codes),
                                                 axis=axis), axis=axis)
    np.testing.assert_array_equal(np.asarray(back), codes)


def test_pack_rejects_odd_axis():
    with pytest.raises(AssertionError):
        packing.pack_int4_planar(jnp.zeros((3, 5), jnp.int8), axis=1)
    with pytest.raises(AssertionError):
        packing.pack_int4(jnp.zeros((7,), jnp.int8), axis=0)


def test_packed_nbytes_odd_shapes():
    assert packing.packed_nbytes((5, 7, 16), axis=-1) == 5 * 7 * 8
    assert packing.packed_nbytes((6, 3), axis=0) == 3 * 3


def test_kv_page_quant_round_trip_properties():
    """Page quantization: extremes round-trip exactly, everything else is
    within half a step, and the all-zero (trash) page stays all-zero."""
    rng = np.random.default_rng(2)
    page = rng.integers(-127, 128, (16, 2, 32)).astype(np.int8)
    page.flat[0] = 127                     # force a known amax
    s = packing.kv_page_scale(jnp.asarray(page))
    assert float(s) == pytest.approx(127.0 / 7.0)
    packed = packing.quantize_kv_page(jnp.asarray(page), s, axis=-1)
    assert packed.shape == (16, 2, 16) and packed.dtype == jnp.uint8
    deq = np.asarray(packing.dequantize_kv_page(packed, s, axis=-1),
                     np.int32)
    assert deq.flat[0] == 127              # amax element exact
    assert np.max(np.abs(deq - page.astype(np.int32))) <= \
        int(np.ceil(float(s) / 2)) + 1
    # trash page: scale well-defined, codes stay zero
    z = jnp.zeros((16, 2, 32), jnp.int8)
    sz = packing.kv_page_scale(z)
    assert float(sz) == pytest.approx(1.0 / 7.0)
    np.testing.assert_array_equal(
        np.asarray(packing.dequantize_kv_page(
            packing.quantize_kv_page(z, sz), sz)), np.zeros((16, 2, 32)))


def test_small_codes_round_trip_exactly():
    """|codes| <= 7 quantize losslessly (scale <= 1 covers the range)."""
    rng = np.random.default_rng(3)
    page = rng.integers(-7, 8, (8, 1, 16)).astype(np.int8)
    page.flat[0] = 7
    s = packing.kv_page_scale(jnp.asarray(page))    # == 1.0
    deq = packing.dequantize_kv_page(
        packing.quantize_kv_page(jnp.asarray(page), s), s)
    np.testing.assert_array_equal(np.asarray(deq), page)


# --- q4 kernels vs oracles -----------------------------------------------------

def _pack_pool(pool_i8):
    """(n_pages, Hkv, P, D) int8 -> packed uint8 pool + (n_pages,) scales,
    the exact per-page shared-scale quantization the write path performs."""
    pool = jnp.asarray(pool_i8)
    scales = jax.vmap(packing.kv_page_scale)(pool)
    packed = jax.vmap(
        lambda p, s: packing.quantize_kv_page(p, s, axis=-1))(pool, scales)
    return packed, scales


def _paged_inputs(b, hkv, g, d, psize, n_pages, nb, lengths, seed=31):
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
    pytest.param(8, [5, 17, 40], None, id="8-lengths2"),     # ragged pages
] + PAGE_RUN_CASES)
def test_paged_decode_q4_bit_exact_vs_oracle(psize, lengths, geom):
    """Fused-dequant paged decode kernel vs the q4 oracle (whole-pool
    dequant + int8 block-online oracle): BIT-EXACT for any page count,
    over the same page runs and slot mixes as the int8 kernel."""
    from repro.kernels.decode_attention import paged_decode_qattention_q4

    if geom is None:
        b, (hkv, g, d), nb = 3, (2, 4, 64), 64 // psize
    else:
        hkv, g, d, psize, nb = geom
        lengths = page_run_lengths(geom, lengths, row=d // 2)
        b = len(lengths)
    n_pages = b * nb + 1
    q, kp, vp, btab, M, sh, s_logit = _paged_inputs(
        b, hkv, g, d, psize, n_pages, nb, lengths)
    kpk, ks = _pack_pool(kp)
    vpk, vs = _pack_pool(vp)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), kpk, vpk, ks, vs, jnp.asarray(btab),
            jnp.asarray(lengths, jnp.int32), jnp.int32(M), jnp.int32(sh),
            lut7, jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    got = np.asarray(paged_decode_qattention_q4(*args, interpret=True),
                     np.int32)
    want = np.asarray(R.paged_decode_qattention_q4_ref(*args), np.int32)
    np.testing.assert_array_equal(got, want)
    # quality sanity: int4 KV stays in the ballpark of the int8-pool answer
    # (random uncorrelated KV is worst-case for a shared page scale, so the
    # bound is loose — real divergence is a reported metric, not an assert)
    i8 = np.asarray(R.paged_decode_qattention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(btab),
        jnp.asarray(lengths, jnp.int32), jnp.int32(M), jnp.int32(sh), lut7,
        jnp.float32(1.0 / s_logit), jnp.float32(1.0)), np.int32)
    assert np.mean(np.abs(got - i8)) < 24.0


@pytest.mark.parametrize("psize,sq,pos0,bq", [
    (16, 16, [0, 16], 16),        # single q block, chunk continuation
    (8, 16, [8, 32], 8),          # multi q block, mid-chain chunks
    (8, 24, [0, 16], 4),          # bq < page, ragged grid mix
    (16, 32, [16, 48], 32),       # chunk spanning several pages
])
def test_paged_prefill_q4_bit_exact_vs_oracle(psize, sq, pos0, bq):
    """Fused-dequant paged prefill kernel vs the q4 oracle: BIT-EXACT for
    any chunk position and q-block size (causal-frontier clamping makes the
    output bq-independent, so autotune can never move bits)."""
    from repro.kernels.prefill_attention import paged_prefill_qattention_q4

    b, h, hkv, d = 2, 4, 2, 64
    pos0 = np.asarray(pos0, np.int32)
    nb = -(-(int(pos0.max()) + sq) // psize) + 1     # + one dead tail block
    rng = np.random.default_rng(37)
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
    kpk, ks = _pack_pool(kp)
    vpk, vs = _pack_pool(vp)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), kpk, vpk, ks, vs, jnp.asarray(btab),
            jnp.asarray(pos0), jnp.int32(M), jnp.int32(sh), lut7,
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    got = np.asarray(paged_prefill_qattention_q4(*args, bq=bq,
                                                 interpret=True), np.int32)
    want = np.asarray(R.paged_prefill_qattention_q4_ref(*args), np.int32)
    np.testing.assert_array_equal(got, want)


def test_q4_ops_dispatch_decode_and_prefill():
    """ops.paged_{decode,prefill}_attention_q4: ref and interpret backends
    agree bit-for-bit (same dispatch contract as the int8 wrappers)."""
    b, hkv, g, d, psize, nb = 2, 1, 2, 32, 8, 4
    q, kp, vp, btab, M, sh, s_logit = _paged_inputs(
        b, hkv, g, d, psize, b * nb + 1, nb, [9, 32], seed=5)
    kpk, ks = _pack_pool(kp)
    vpk, vs = _pack_pool(vp)
    lut7 = jnp.asarray(R.make_exp_lut_q7())
    args = (jnp.asarray(q), kpk, vpk, ks, vs, jnp.asarray(btab),
            jnp.asarray([9, 32], jnp.int32), jnp.int32(M), jnp.int32(sh),
            lut7, jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    a = ops.paged_decode_attention_q4(*args, impl="ref")
    c = ops.paged_decode_attention_q4(*args, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    sq = 16
    pos0 = np.asarray([0, 8], np.int32)
    rng = np.random.default_rng(7)
    qp = rng.integers(-64, 65, (b, 2, sq, d)).astype(np.int8)
    btab2 = np.zeros((b, nb), np.int32)
    perm = iter(range(1, b * nb + 1))
    for bb in range(b):
        for i in range(-(-(int(pos0[bb]) + sq) // psize)):
            btab2[bb, i] = next(perm)
    pargs = (jnp.asarray(qp), kpk, vpk, ks, vs, jnp.asarray(btab2),
             jnp.asarray(pos0), jnp.int32(M), jnp.int32(sh), lut7,
             jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    a = ops.paged_prefill_attention_q4(*pargs, impl="ref")
    c = ops.paged_prefill_attention_q4(*pargs, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

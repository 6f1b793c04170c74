"""W4A8 tiled matmul Pallas kernel — the TPU realization of the paper's PE/BIM.

The accelerator's job split, re-expressed for TPU:

* HBM holds weights **nibble-packed** (two int4 codes per byte, K-planar
  layout = the paper's Type-A BIM data rearrangement): half the weight-stream
  bytes of an int8 model, 1/4 of bf16 — this is where the 7.94x compression
  pays at serving time.
* The Pallas grid pipeline double-buffers packed tiles HBM->VMEM (the paper's
  double-buffered weight buffer overlapping AXI transfers).
* In VMEM each packed tile is sign-extended into two int8 nibble planes and
  fed to the MXU (the BIM's 8x4 multipliers; the MXU consumes int8, so a
  4-bit value rides for free).
* The int32 accumulator lives in a VMEM scratch across the K grid dimension
  (the paper's Psum Buf), and the epilogue on the last K step adds the int32
  bias and applies the 32-bit fixed-point requantizer (paper Eq. 5) — the
  "quantization module" after the accumulator in Fig. 2.

The 8x8 path (``int8_bitsplit``) computes an 8-bit-weight matmul as two
nibble matmuls combined by shift-add — bit-for-bit the BIM Type-A identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint as fxp

# Default MXU-aligned tile sizes (v5e: 128x128 MXU, ~16 MB VMEM/core).
BM, BN, BK2 = 128, 128, 256  # BK2 = packed K rows per step = BK // 2


def _sign_extend(nib: jax.Array) -> jax.Array:
    """uint4-in-int32 [0,15] -> signed [-8,7] (branch-free)."""
    return ((nib ^ 8) - 8).astype(jnp.int8)


def _int4_matmul_kernel(x_lo_ref, x_hi_ref, w_ref, b_ref, m_ref, s_ref,
                        o_ref, acc_ref):
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w32 = w_ref[...].astype(jnp.int32)
    w_lo = _sign_extend(w32 & 15)        # rows [0, K/2): low-nibble plane
    w_hi = _sign_extend((w32 >> 4) & 15) # rows [K/2, K): high-nibble plane
    dn = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(x_lo_ref[...], w_lo, dn,
                              preferred_element_type=jnp.int32)
    acc += jax.lax.dot_general(x_hi_ref[...], w_hi, dn,
                               preferred_element_type=jnp.int32)
    acc_ref[...] += acc

    @pl.when(k_i == nk - 1)
    def _epilogue():
        total = acc_ref[...] + b_ref[...].astype(jnp.int32)
        y = fxp.requantize(total, m_ref[0], s_ref[0], bits=8)
        o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk2", "interpret"))
def int4_matmul(
    x_i8: jax.Array,      # int8 (M, K)
    w_packed: jax.Array,  # uint8 (K//2, N) K-planar packed
    bias_i32: jax.Array,  # int32 (N,)
    M_q: jax.Array,       # () or (1,) int32 fixed-point multiplier
    shift_q: jax.Array,
    *,
    bm: int = BM,
    bn: int = BN,
    bk2: int = BK2,
    interpret: bool = False,
) -> jax.Array:
    m, k = x_i8.shape
    k2, n = w_packed.shape
    assert k == 2 * k2, (k, k2)
    bm = min(bm, m)
    bn = min(bn, n)
    bk2 = min(bk2, k2)
    assert m % bm == 0 and n % bn == 0 and k2 % bk2 == 0, (m, n, k2, bm, bn, bk2)
    nk = k2 // bk2
    grid = (m // bm, n // bn, nk)

    return pl.pallas_call(
        _int4_matmul_kernel,
        grid=grid,
        in_specs=[
            # x column blocks [0, K/2) — pair row r of the packed tile
            pl.BlockSpec((bm, bk2), lambda i, j, t: (i, t)),
            # x column blocks [K/2, K) — pair row r's HIGH nibbles
            pl.BlockSpec((bm, bk2), lambda i, j, t, nk=nk: (i, t + nk)),
            pl.BlockSpec((bk2, bn), lambda i, j, t: (t, j)),
            # bias as a (1, N) row: a 1-D block of it is refused on TPU
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_i8, x_i8, w_packed, bias_i32.reshape(1, n),
      jnp.asarray(M_q, jnp.int32).reshape(1), jnp.asarray(shift_q, jnp.int32).reshape(1))


def _bitsplit_kernel(x_ref, w_ref, b_ref, m_ref, s_ref, o_ref, acc_ref):
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w32 = w_ref[...].astype(jnp.int32)
    hi = (w32 >> 4).astype(jnp.int8)   # signed high nibble (arithmetic shift)
    lo = (w32 & 15).astype(jnp.int8)   # unsigned low nibble
    x = x_ref[...]
    dn = (((1,), (0,)), ((), ()))
    acc_hi = jax.lax.dot_general(x, hi, dn, preferred_element_type=jnp.int32)
    acc_lo = jax.lax.dot_general(x, lo, dn, preferred_element_type=jnp.int32)
    acc_ref[...] += (acc_hi << 4) + acc_lo   # BIM Type-A shift-add

    @pl.when(k_i == nk - 1)
    def _epilogue():
        total = acc_ref[...] + b_ref[...].astype(jnp.int32)
        o_ref[...] = fxp.requantize(total, m_ref[0], s_ref[0], bits=8)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_bitsplit_matmul(
    x_i8: jax.Array,   # int8 (M, K)
    w_i8: jax.Array,   # int8 (K, N)
    bias_i32: jax.Array,
    M_q: jax.Array,
    shift_q: jax.Array,
    *,
    bm: int = BM,
    bn: int = BN,
    bk: int = 2 * BK2,
    interpret: bool = False,
) -> jax.Array:
    m, k = x_i8.shape
    k_, n = w_i8.shape
    assert k == k_
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _bitsplit_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_i8, w_i8, bias_i32.reshape(1, n),
      jnp.asarray(M_q, jnp.int32).reshape(1), jnp.asarray(shift_q, jnp.int32).reshape(1))

"""int4 nibble packing — the storage format the accelerator streams from HBM.

The paper's 7.94x compression comes from 4-bit weights in off-chip memory.
On TPU the analogous win is HBM bytes: we pack two signed 4-bit codes per
int8 lane.  Two layouts are provided:

* ``pack_int4`` — adjacent-pair layout: codes (..., 2i) and (..., 2i+1) share a
  byte.  Natural for storage; unpack interleaves.
* ``pack_int4_planar`` — nibble-planar layout: the LOW nibbles of the first
  half of the axis and HIGH nibbles of the second half.  This is the Type-A
  BIM trick from the paper (Fig. 4): "using shift logic at adder tree's output
  can save more resources, though this need to rearrange the input data".
  On TPU the rearrangement means unpacking produces two CONTIGUOUS int8 tiles
  (no interleave shuffle), which lowers to cheap vector ops in Pallas.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.boundary import kernel_boundary


def pack_int4(codes: jax.Array, axis: int = -1) -> jax.Array:
    """Pack signed int4 codes (stored in int8, range [-8,7]) two per uint8.

    codes.shape[axis] must be even; the packed axis has half the length.
    Byte layout: low nibble = even index, high nibble = odd index.
    """
    axis = axis % codes.ndim
    assert codes.shape[axis] % 2 == 0, "pack axis must be even-sized"
    lo = jnp.take(codes, jnp.arange(0, codes.shape[axis], 2), axis=axis)
    hi = jnp.take(codes, jnp.arange(1, codes.shape[axis], 2), axis=axis)
    lo_u = lo.astype(jnp.uint8) & 0xF
    hi_u = (hi.astype(jnp.uint8) & 0xF) << 4
    return lo_u | hi_u


def unpack_int4(packed: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse of pack_int4: uint8 -> sign-extended int8 codes, axis doubled."""
    axis = axis % packed.ndim
    lo = _sign_extend_nibble(packed & 0xF)
    hi = _sign_extend_nibble((packed >> 4) & 0xF)
    stacked = jnp.stack([lo, hi], axis=axis + 1)  # (..., n, 2, ...)
    new_shape = list(packed.shape)
    new_shape[axis] *= 2
    return stacked.reshape(new_shape)


def pack_int4_planar(codes: jax.Array, axis: int = 0) -> jax.Array:
    """Nibble-planar pack: first half of ``axis`` -> low nibbles, second half
    -> high nibbles (Type-A BIM data rearrangement)."""
    axis = axis % codes.ndim
    n = codes.shape[axis]
    assert n % 2 == 0
    first, second = jnp.split(codes, 2, axis=axis)
    lo_u = first.astype(jnp.uint8) & 0xF
    hi_u = (second.astype(jnp.uint8) & 0xF) << 4
    return lo_u | hi_u


def unpack_int4_planar(packed: jax.Array, axis: int = 0) -> jax.Array:
    """Inverse of pack_int4_planar: concatenates the two nibble planes."""
    lo = _sign_extend_nibble(packed & 0xF)
    hi = _sign_extend_nibble((packed >> 4) & 0xF)
    return jnp.concatenate([lo, hi], axis=axis % packed.ndim)


def _sign_extend_nibble(u4: jax.Array) -> jax.Array:
    """uint8 holding a nibble in [0,15] -> signed int8 in [-8,7].

    Branch-free: (x ^ 8) - 8 maps 0..7 -> 0..7 and 8..15 -> -8..-1.
    """
    x = u4.astype(jnp.int8)
    return (x ^ jnp.int8(8)) - jnp.int8(8)


def packed_nbytes(shape, axis: int = -1) -> int:
    """Bytes of the packed representation of an int4 tensor of ``shape``."""
    n = 1
    for i, d in enumerate(shape):
        n *= d // 2 if i == axis % len(shape) else d
    return n


# --- int4-packed KV pages (shared scale per page) ------------------------------
#
# The serving KV pool stores int8 codes; at ``kv_bits=4`` each page is
# re-quantized to signed 4-bit codes under ONE shared fp32 scale per page
# and nibble-packed planar along the head dim (pages hold half the bytes).
# The helpers below define the quantize/dequantize contract that the write
# path (models/serve_int.py), the fused-dequant attention kernels, and the
# bit-exact oracles (kernels/ref.py) all share: any drift between them
# breaks the kernel-vs-oracle equality tests.

KV4_QMAX = 7  # symmetric int4 target range: codes in [-7, 7] (+-8 unused
              # by the scale so dequant round-trips the extremes exactly)


def kv_page_scale(codes_i8: jax.Array) -> jax.Array:
    """Shared dequant scale for one page of int8 KV codes.

    ``max(amax(|codes|), 1) / 7`` — the ``max(.., 1)`` keeps an all-zero
    page (trash page, never-written tail rows) at a well-defined scale
    instead of dividing by zero.  fp32 scalar.
    """
    amax = jnp.max(jnp.abs(codes_i8.astype(jnp.int32)))
    return jnp.maximum(amax, 1).astype(jnp.float32) / KV4_QMAX


def quantize_kv_page(codes_i8: jax.Array, scale: jax.Array,
                     axis: int = -1) -> jax.Array:
    """int8 KV codes -> planar nibble-packed uint8 under a shared scale.

    ``c4 = clip(round(c8 / scale), -8, 7)``, then ``pack_int4_planar`` along
    ``axis`` (the head dim for the KV pool) — the packed axis halves.
    """
    c4 = jnp.clip(jnp.round(codes_i8.astype(jnp.float32) / scale), -8, 7)
    return pack_int4_planar(c4.astype(jnp.int8), axis=axis)


def dequant_int4_codes(c4_i8: jax.Array, scale: jax.Array) -> jax.Array:
    """int4 codes (in int8 storage) -> int8 codes: clip(round(c4*scale)).

    THE dequant formula: the Pallas kernels fuse exactly this (sign-extend,
    fp32 multiply by the page scale, round, clip) into their inner loop.
    """
    y = jnp.round(c4_i8.astype(jnp.float32) * scale)
    return jnp.clip(y, -127, 127).astype(jnp.int8)


def dequantize_kv_page(packed_u8: jax.Array, scale: jax.Array,
                       axis: int = -1) -> jax.Array:
    """Inverse of ``quantize_kv_page`` (lossy at 4 bits): unpack + dequant."""
    return dequant_int4_codes(unpack_int4_planar(packed_u8, axis=axis), scale)


@kernel_boundary(why="whole-pool int4 dequant for the bit-exact jnp "
                     "oracles; the Pallas kernels do this per tile in VMEM")
def dequantize_kv_pool(packed_pool_u8: jax.Array,
                       page_scales: jax.Array) -> jax.Array:
    """Whole-pool dequant: (n_pages, Hkv, P, hd//2) uint8 + (n_pages,) fp32
    -> (n_pages, Hkv, P, hd) int8.  Used by the jnp fallback paths and the
    kernel oracles — NOT by the Pallas kernels, which dequantize per tile
    in VMEM and never materialize this view.  Registered as a kernel
    boundary: the pool-scale float cast inside is the audited exemption."""
    c4 = unpack_int4_planar(packed_pool_u8, axis=-1)
    return dequant_int4_codes(c4, page_scales[:, None, None, None])

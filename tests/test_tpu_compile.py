"""Ahead-of-time TPU compiles of the serving kernels at qwen3-4b widths.

No chip is needed: the TPU compiler compiles for a described v5e topology,
so each test lowers one Pallas kernel for a described chip and fails on
exactly what Mosaic would refuse on the real one (block shapes off the
(8, 128) tiling, layout mismatches, VMEM overflow).  Nothing runs; these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.qsoftmax import LUT_SIZE

CFG = get_config("qwen3-4b")
D, NH, NKV, HD, DFF, V = (CFG.d_model, CFG.n_heads, CFG.n_kv_heads, CFG.hd,
                          CFG.d_ff, CFG.vocab_size)
G = NH // NKV
ROWS = 8              # decode rows: batch_slots padded to the sublane tile
PAGE, N_PAGES, NB = 16, 512, 32
SLOTS = 4
CHUNK = 256           # prefill chunk rows


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    # a compiled Pallas kernel lowers to a Mosaic custom call; interpret
    # mode would have expanded into plain HLO instead
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _scalars(sds):
    return (sds((1,), jnp.int32), sds((1,), jnp.int32))


@pytest.mark.parametrize("k,n", [
    (D, NH * HD),        # q projection
    (D, NKV * HD),       # k / v projections
    (D, DFF),            # gate / up
    (DFF, D),            # down
    (D, V),              # lm_head
], ids=["wq", "wkv", "gate_up", "down", "lm_head"])
def test_int4_matmul_compiles(sds, k, n):
    from repro.kernels.int4_matmul import int4_matmul
    _compile(functools.partial(int4_matmul, interpret=False),
             sds((ROWS, k), jnp.int8), sds((k // 2, n), jnp.uint8),
             sds((n,), jnp.int32), *_scalars(sds))


def test_quant_layernorm_compiles(sds):
    from repro.kernels.quant_layernorm import quant_layernorm
    _compile(functools.partial(quant_layernorm, subtract_mean=False,
                               interpret=False),
             sds((ROWS, D), jnp.int8), sds((D,), jnp.int8),
             sds((D,), jnp.int32), *_scalars(sds))


def _attn_tail(sds):
    return (sds((1,), jnp.int32), sds((1,), jnp.int32),
            sds((LUT_SIZE,), jnp.int32), sds((1,), jnp.float32),
            sds((1,), jnp.float32))


# the benchmark cells' paged decode shapes (slots, kv heads, group, head
# dim, pages, table length): qwen3-4b.decode and stablelm-1.6b.longctx
DECODE_CELL = (64, NKV, G, HD, 4097, 96)
LONGCTX_CELL = (8, 32, 1, 64, 2049, 256)


@pytest.mark.parametrize("kv_bits,shape", [
    pytest.param(8, None, id="8"),
    pytest.param(4, None, id="4"),
    pytest.param(8, DECODE_CELL, id="8-decode_cell"),
    pytest.param(4, DECODE_CELL, id="4-decode_cell"),
    pytest.param(8, LONGCTX_CELL, id="8-longctx_cell"),
    pytest.param(4, LONGCTX_CELL, id="4-longctx_cell"),
])
def test_paged_decode_compiles(sds, kv_bits, shape):
    from repro.kernels import decode_attention as DA
    slots, nkv, g, hd, n_pages, nb = shape or (SLOTS, NKV, G, HD, N_PAGES,
                                                NB)
    q = sds((slots, nkv, g, hd), jnp.int8)
    btab, lens = sds((slots, nb), jnp.int32), sds((slots,), jnp.int32)
    if kv_bits == 8:
        pool = sds((n_pages, nkv, PAGE, hd), jnp.int8)
        _compile(functools.partial(DA.paged_decode_qattention,
                                   interpret=False),
                 q, pool, pool, btab, lens, *_attn_tail(sds))
    else:
        pool = sds((n_pages, nkv, PAGE, hd // 2), jnp.uint8)
        scale = sds((n_pages,), jnp.float32)
        _compile(functools.partial(DA.paged_decode_qattention_q4,
                                   interpret=False),
                 q, pool, pool, scale, scale, btab, lens, *_attn_tail(sds))


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_paged_prefill_compiles(sds, kv_bits):
    from repro.kernels import prefill_attention as PA
    q = sds((1, NH, CHUNK, HD), jnp.int8)
    btab, pos0 = sds((1, NB), jnp.int32), sds((1,), jnp.int32)
    if kv_bits == 8:
        pool = sds((N_PAGES, NKV, PAGE, HD), jnp.int8)
        _compile(functools.partial(PA.paged_prefill_qattention,
                                   interpret=False),
                 q, pool, pool, btab, pos0, *_attn_tail(sds))
    else:
        pool = sds((N_PAGES, NKV, PAGE, HD // 2), jnp.uint8)
        scale = sds((N_PAGES,), jnp.float32)
        _compile(functools.partial(PA.paged_prefill_qattention_q4,
                                   interpret=False),
                 q, pool, pool, scale, scale, btab, pos0, *_attn_tail(sds))

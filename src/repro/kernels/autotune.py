"""Per-shape tile-size selection for the attention kernels.

``benchmarks/roofline.py`` and ``benchmarks/hlo_cost.py`` can price a kernel
but fed no kernel decisions until now: the decode kernel always ran
``bkv=512`` and the paged prefill kernel ``bq=128`` regardless of batch
size, page size, head geometry, or KV bit width.  This module closes the
loop with a tiny roofline-derived cost table:

* ``decode_bkv(...)``  — KV tile length for the contiguous decode kernel.
* ``prefill_bq(...)``  — q-block length for the paged prefill kernel.

Selections are cached per shape key, overridable by environment
(``REPRO_DECODE_BKV`` / ``REPRO_PREFILL_BQ`` pin a value,
``REPRO_AUTOTUNE=off`` restores the legacy fixed defaults), and — because
the paged kernels' dead-block clamping makes their outputs tile-size
independent (see the kernel docstrings) — NEVER change numerics: autotune
moves DMA/grid overhead around, not bits.

The cost model prices the chip the kernels run on (``hw_constants``, keyed
by device kind; off the TPU the kernels' target, v5e).  A grid step costs
``max(tile_bytes / hbm_bw, tile_flops / peak_int8_ops)`` plus a
fixed per-step overhead (DMA issue + grid bookkeeping); fewer, larger steps
amortize the overhead until the double-buffered tiles overflow the VMEM
budget.  For prefill, every KV page is streamed once per (head, q-block),
so the KV traffic itself scales with ``ceil(sq / bq)`` — the dominant term
for long chains at big batch.

``REPRO_AUTOTUNE=measure`` is the opt-in measured mode: instead of pricing
candidates with the cost table, ``decode_bkv``/``prefill_bq`` build
synthetic int8 inputs for the exact shape being asked about, race every
candidate tile through the live kernel on the real backend
(``measure_best``), and cache the per-shape winner under the same
key/override discipline.  The env pins still take precedence and the
analytic table remains the default: measured mode pays one compile+run
per candidate per shape at first touch (a deliberate compile storm), which
is right for benchmarks pinning a deployment shape and wrong for cold
serving starts.  Shapes the measured path cannot race (an int4 contiguous
decode has no kernel; synthetic pools over the memory guard) fall back to
the roofline pick.  Either way numerics never move — tile size only
relocates DMA/grid overhead.
"""
from __future__ import annotations

import os

import jax

# chip ceilings — shared with benchmarks/roofline.py and the analysis
# lane's VMEM lint via kernels/hw_constants (drift-tested)
from repro.kernels import hw_constants as HW

DECODE_BKV_CANDIDATES = (128, 256, 512, 1024)
# 8/16 exist for the small ragged batches the speculative verify forward
# sends through the paged prefill kernel (sq = spec_k+1); divisor-fitting
# collapses them for ordinary chunk sizes, the roofline model prices them
# out for long chains
PREFILL_BQ_CANDIDATES = (8, 16, 32, 64, 128, 256)

DEFAULT_DECODE_BKV = 512     # legacy fixed defaults (REPRO_AUTOTUNE=off)
DEFAULT_PREFILL_BQ = 128

_cache: dict = {}


def clear_cache() -> None:
    _cache.clear()


def target_chip() -> HW.Chip:
    """Ceilings of the chip the kernels run on.  Off the TPU they run in
    interpret mode (or as jnp oracles) and still target a v5e, so that
    entry is named explicitly there."""
    dev = jax.devices()[0]
    return HW.chip(dev.device_kind) if dev.platform == "tpu" else HW.V5E


def _mode() -> str:
    return os.environ.get("REPRO_AUTOTUNE", "roofline")


def _env_int(name: str):
    v = os.environ.get(name)
    return int(v) if v else None


def _fit(c: int, n: int) -> int:
    """Largest divisor of ``n`` that is <= c (mirrors divisor_tile)."""
    c = min(c, n)
    while n % c:
        c -= 1
    return c


def _kv_bytes(hd: int, kv_bits: int) -> float:
    return hd * (0.5 if kv_bits == 4 else 1.0)


def decode_bkv(smax: int, *, batch_slots: int, hkv: int, hd: int,
               kv_bits: int = 8) -> int:
    """KV tile length for the contiguous decode kernel at this shape."""
    env = _env_int("REPRO_DECODE_BKV")
    if env:
        return _fit(env, smax)
    if _mode() == "off":
        return _fit(DEFAULT_DECODE_BKV, smax)
    key = ("decode_bkv", batch_slots, hkv, hd, smax, kv_bits)
    if _mode() == "measure":
        got = _measured_decode_bkv(("measure",) + key, smax,
                                   batch_slots=batch_slots, hkv=hkv, hd=hd,
                                   kv_bits=kv_bits)
        if got is not None:
            return got                    # else: fall back to the model
    got = _cache.get(key)
    if got is None:
        got = _roofline_pick(
            DECODE_BKV_CANDIDATES, smax,
            tile_bytes=lambda bkv: 2 * bkv * _kv_bytes(hd, kv_bits),
            tile_flops=lambda bkv: 2 * 2 * bkv * hd,       # QK^T + P@V
            steps=lambda bkv: batch_slots * hkv * (smax // bkv),
        )
        _cache[key] = got
    return got


def prefill_bq(sq: int, *, batch_slots: int, page_size: int, hkv: int,
               hd: int, kv_bits: int = 8, n_blocks: int = 1,
               n_heads: int | None = None) -> int:
    """q-block length for the paged prefill kernel at this shape.

    Safe to vary freely: block-level causal skipping makes the kernel
    output bq-independent, so two engines tuned differently still agree
    bit-for-bit.
    """
    env = _env_int("REPRO_PREFILL_BQ")
    if env:
        return _fit(env, sq)
    if _mode() == "off":
        return _fit(DEFAULT_PREFILL_BQ, sq)
    h = n_heads or hkv
    key = ("prefill_bq", batch_slots, page_size, hkv, hd, sq, kv_bits,
           n_blocks, h)
    if _mode() == "measure":
        got = _measured_prefill_bq(("measure",) + key, sq,
                                   batch_slots=batch_slots,
                                   page_size=page_size, hkv=hkv, hd=hd,
                                   kv_bits=kv_bits, n_blocks=n_blocks,
                                   n_heads=h)
        if got is not None:
            return got                    # else: fall back to the model
    got = _cache.get(key)
    if got is None:
        kvb = page_size * _kv_bytes(hd, kv_bits)
        got = _roofline_pick(
            PREFILL_BQ_CANDIDATES, sq,
            # each page streams once per (head, q-block): q tile + KV page
            tile_bytes=lambda bq: bq * hd + 2 * kvb,
            tile_flops=lambda bq: 2 * 2 * bq * page_size * hd,
            steps=lambda bq: batch_slots * h * (sq // bq) * n_blocks,
            extra_vmem=lambda bq: 2 * bq * hd * 4,          # fp32 scratch
        )
        _cache[key] = got
    return got


def _roofline_pick(candidates, n, *, tile_bytes, tile_flops, steps,
                   extra_vmem=lambda c: 0) -> int:
    """Pick the candidate minimizing modeled wall time within VMEM budget."""
    hw = target_chip()
    best, best_t = None, None
    for raw in candidates:
        c = _fit(raw, n)
        # double-buffered in/out tiles must fit the fill fraction of VMEM
        if 2 * tile_bytes(c) + extra_vmem(c) > hw.vmem_budget * HW.VMEM_FILL:
            continue
        t = steps(c) * (HW.STEP_OVERHEAD_S +
                        max(tile_bytes(c) / hw.hbm_bw,
                            tile_flops(c) / hw.peak_int8_ops))
        if best_t is None or t < best_t or (t == best_t and c > best):
            best, best_t = c, t
    if best is None:                      # every candidate overflowed VMEM
        best = _fit(candidates[0], n)
    return best


def measure_best(candidates, timer, *, key=None):
    """Measured mode core: time ``timer(candidate)`` (seconds) over the
    candidate set and cache the argmin under ``key``.  Drives the
    ``REPRO_AUTOTUNE=measure`` paths below and is usable directly by
    benchmarks; returns the winning candidate."""
    if key is not None and key in _cache:
        return _cache[key]
    best, best_t = None, None
    for c in candidates:
        t = timer(c)
        if best_t is None or t < best_t:
            best, best_t = c, t
    if key is not None:
        _cache[key] = best
    return best


# --- REPRO_AUTOTUNE=measure: race candidates through the live kernels ----

MEASURE_REPS = 3                 # timed reps per candidate (after 1 warmup)
MEASURE_BYTES_CAP = 2 << 30      # skip measuring shapes needing > 2 GiB


def _timed_call(fn, reps=MEASURE_REPS) -> float:
    """Mean wall seconds per call; one untimed call first eats the
    compile + warmup."""
    import time

    import jax
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def _attn_quant_meta():
    """Plausible softmax requant metadata for synthetic timing inputs (the
    values move bits, never runtime)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fixedpoint as fxp
    from repro.core import qsoftmax as qs
    from repro.kernels import ref
    s_logit = 1.0 / (0.05 * np.sqrt(64))
    M, sh = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    return (jnp.int32(M), jnp.int32(sh), jnp.asarray(ref.make_exp_lut_q7()),
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))


def _measured_decode_bkv(key, smax, *, batch_slots, hkv, hd, kv_bits):
    """Race DECODE_BKV_CANDIDATES through the contiguous decode kernel on
    synthetic int8 inputs at this exact shape.  Returns None (-> roofline
    fallback) for shapes with no raceable kernel (int4 contiguous decode
    does not exist) or over the memory guard."""
    if key in _cache:
        return _cache[key]
    if kv_bits != 8 or 2 * batch_slots * smax * hkv * hd > MEASURE_BYTES_CAP:
        return None
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-64, 65, (batch_slots, hkv, 1, hd)),
                    jnp.int8)
    k = jnp.asarray(rng.integers(-64, 65, (batch_slots, smax, hkv, hd)),
                    jnp.int8)
    v = jnp.asarray(rng.integers(-64, 65, (batch_slots, smax, hkv, hd)),
                    jnp.int8)
    lengths = jnp.full((batch_slots,), smax, jnp.int32)
    meta = _attn_quant_meta()
    cands = tuple(dict.fromkeys(_fit(c, smax)
                                for c in DECODE_BKV_CANDIDATES))
    return measure_best(
        cands,
        lambda c: _timed_call(
            lambda: ops.decode_attention_q(q, k, v, lengths, *meta, bkv=c)),
        key=key)


def _measured_prefill_bq(key, sq, *, batch_slots, page_size, hkv, hd,
                         kv_bits, n_blocks, n_heads):
    """Race PREFILL_BQ_CANDIDATES through the paged prefill kernel (int8 or
    int4-packed to match ``kv_bits``) on a synthetic full-chain workload:
    every slot's block table maps ``n_blocks`` distinct pages and the chunk
    sits at the chain's end, so each candidate pays the worst-case KV
    restream the roofline model prices."""
    if key in _cache:
        return _cache[key]
    n_pages = batch_slots * n_blocks + 1
    kvb = int(page_size * _kv_bytes(hd, kv_bits))
    if 2 * n_pages * kvb * hkv > MEASURE_BYTES_CAP:
        return None
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-64, 65, (batch_slots, n_heads, sq, hd)),
                    jnp.int8)
    btab = jnp.asarray(
        1 + np.arange(batch_slots * n_blocks, dtype=np.int32)
        .reshape(batch_slots, n_blocks))
    pos0 = jnp.full((batch_slots,), n_blocks * page_size - sq, jnp.int32)
    meta = _attn_quant_meta()
    if kv_bits == 4:
        pool = lambda: jnp.asarray(
            rng.integers(0, 256, (n_pages, hkv, page_size, hd // 2)),
            jnp.uint8)
        kp, vp = pool(), pool()
        scale = jnp.full((n_pages,), 0.05, jnp.float32)
        run = lambda c: ops.paged_prefill_attention_q4(
            q, kp, vp, scale, scale, btab, pos0, *meta, bq=c)
    else:
        pool = lambda: jnp.asarray(
            rng.integers(-64, 65, (n_pages, hkv, page_size, hd)), jnp.int8)
        kp, vp = pool(), pool()
        run = lambda c: ops.paged_prefill_attention_q(
            q, kp, vp, btab, pos0, *meta, bq=c)
    cands = tuple(dict.fromkeys(_fit(c, sq) for c in PREFILL_BQ_CANDIDATES))
    return measure_best(cands, lambda c: _timed_call(lambda: run(c)),
                        key=key)

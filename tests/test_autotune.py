"""kernels/autotune.py: roofline-derived tile selection, caching, overrides.

Autotune only moves DMA/grid overhead around — the paged kernels' outputs
are tile-size independent (dead-block clamping) — so these tests check the
selection MACHINERY: picked values are legal (candidate-derived divisors),
cached per shape, overridable by env, and the off switch restores the
legacy fixed defaults.
"""
import pytest

from repro.kernels import autotune


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("REPRO_DECODE_BKV", "REPRO_PREFILL_BQ", "REPRO_AUTOTUNE"):
        monkeypatch.delenv(var, raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def test_decode_bkv_legal_divisor():
    for smax in (64, 512, 2048, 96):
        got = autotune.decode_bkv(smax, batch_slots=8, hkv=8, hd=128)
        assert smax % got == 0 and got >= 1
        assert got <= max(autotune.DECODE_BKV_CANDIDATES)


def test_prefill_bq_legal_divisor():
    for sq in (16, 128, 384):
        got = autotune.prefill_bq(sq, batch_slots=8, page_size=16, hkv=8,
                                  hd=128, n_blocks=32, n_heads=32)
        assert sq % got == 0 and got >= 1
        assert got <= max(autotune.PREFILL_BQ_CANDIDATES)


def test_bigger_q_blocks_for_long_chains():
    """The KV-restream term dominates for long chains: each page streams
    once per q-block, so the model must not pick a tiny bq when the chain
    is long (it would multiply KV traffic)."""
    big = autotune.prefill_bq(256, batch_slots=8, page_size=16, hkv=8,
                              hd=128, n_blocks=128, n_heads=32)
    assert big >= 128


def test_selection_cached_per_shape():
    k = ("decode_bkv", 4, 8, 128, 1024, 8)
    autotune.decode_bkv(1024, batch_slots=4, hkv=8, hd=128)
    assert k in autotune._cache
    autotune._cache[k] = 128          # poison: cache hit must win
    assert autotune.decode_bkv(1024, batch_slots=4, hkv=8, hd=128) == 128
    autotune.clear_cache()
    assert autotune.decode_bkv(1024, batch_slots=4, hkv=8, hd=128) != 128 \
        or autotune._cache[k] != 128


def test_env_override_pins_value(monkeypatch):
    monkeypatch.setenv("REPRO_DECODE_BKV", "256")
    assert autotune.decode_bkv(1024, batch_slots=4, hkv=8, hd=128) == 256
    # override still divisor-fitted to the actual length
    assert autotune.decode_bkv(96, batch_slots=4, hkv=8, hd=128) == 96
    monkeypatch.setenv("REPRO_PREFILL_BQ", "64")
    assert autotune.prefill_bq(128, batch_slots=4, page_size=16, hkv=8,
                               hd=128) == 64


def test_off_mode_restores_legacy_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.decode_bkv(1024, batch_slots=4, hkv=8, hd=128) == \
        autotune.DEFAULT_DECODE_BKV
    assert autotune.prefill_bq(256, batch_slots=4, page_size=16, hkv=8,
                               hd=128) == autotune.DEFAULT_PREFILL_BQ


def test_kv4_halves_tile_bytes():
    """A 4-bit pool halves page bytes — selections stay legal and the key
    space distinguishes bit widths (no cross-contamination)."""
    a = autotune.decode_bkv(2048, batch_slots=8, hkv=8, hd=128, kv_bits=8)
    b = autotune.decode_bkv(2048, batch_slots=8, hkv=8, hd=128, kv_bits=4)
    assert 2048 % a == 0 and 2048 % b == 0
    keys = {k for k in autotune._cache if k[0] == "decode_bkv"}
    assert len(keys) == 2


def test_measure_mode_decode_bkv(monkeypatch):
    """REPRO_AUTOTUNE=measure races the live decode kernel: the pick is a
    legal candidate-derived divisor, cached per shape (second call runs no
    kernels — asserted by poisoning the cache), and env pins still win."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    got = autotune.decode_bkv(128, batch_slots=2, hkv=2, hd=64)
    assert 128 % got == 0 and got >= 1
    key = ("measure", "decode_bkv", 2, 2, 64, 128, 8)
    assert autotune._cache[key] == got
    autotune._cache[key] = 64            # poison: cache hit must win
    assert autotune.decode_bkv(128, batch_slots=2, hkv=2, hd=64) == 64
    monkeypatch.setenv("REPRO_DECODE_BKV", "32")
    assert autotune.decode_bkv(128, batch_slots=2, hkv=2, hd=64) == 32


def test_measure_mode_prefill_bq(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    got = autotune.prefill_bq(16, batch_slots=2, page_size=8, hkv=2, hd=64,
                              n_blocks=4, n_heads=2)
    assert 16 % got == 0 and got >= 1
    key = ("measure", "prefill_bq", 2, 8, 2, 64, 16, 8, 4, 2)
    assert autotune._cache[key] == got


def test_measure_mode_falls_back_without_kernel(monkeypatch):
    """An int4 contiguous decode has no kernel to race: measured mode must
    fall back to the roofline pick instead of crashing."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    got = autotune.decode_bkv(256, batch_slots=2, hkv=2, hd=64, kv_bits=4)
    assert 256 % got == 0 and got >= 1
    assert not any(k[0] == "measure" for k in autotune._cache)


def test_small_bq_candidates_stay_priced_out():
    """The 8/16 candidates added for speculative verify shapes must not
    leak into ordinary long-chain chunk tuning (KV restream dominates)."""
    big = autotune.prefill_bq(256, batch_slots=8, page_size=16, hkv=8,
                              hd=128, n_blocks=128, n_heads=32)
    assert big >= 128
    # tiny verify-shaped sq: every candidate divisor-fits to sq
    small = autotune.prefill_bq(4, batch_slots=8, page_size=16, hkv=8,
                                hd=128, n_blocks=8, n_heads=32)
    assert small in (1, 2, 4)


def test_measure_best_caches_argmin():
    times = {32: 3.0, 64: 1.0, 128: 2.0}
    calls = []

    def timer(c):
        calls.append(c)
        return times[c]

    got = autotune.measure_best((32, 64, 128), timer, key=("m", 1))
    assert got == 64
    assert autotune.measure_best((32, 64, 128), timer, key=("m", 1)) == 64
    assert len(calls) == 3            # second call served from cache


def test_hw_constants_single_source_no_drift():
    """Satellite of the analysis PR: the roofline constants live ONCE in
    kernels/hw_constants.py; both consumers (the tuner and the
    benchmarks/roofline.py model) must resolve to the very same objects —
    a re-declared copy in either file is exactly the drift this pins."""
    import importlib.util
    from pathlib import Path

    from repro.analysis import pallas_lint
    from repro.kernels import hw_constants as HW

    assert autotune.HW is HW
    # off the TPU the tuner prices the kernels' target chip, named explicitly
    assert autotune.target_chip() is HW.V5E
    assert pallas_lint.VMEM_BUDGET is HW.V5E.vmem_budget
    assert pallas_lint.VMEM_FILL is HW.VMEM_FILL

    roofline_py = (Path(__file__).resolve().parents[1] / "benchmarks"
                   / "roofline.py")
    spec = importlib.util.spec_from_file_location("roofline", roofline_py)
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    assert roofline.CHIP is HW.V5E


def test_hw_constants_keyed_by_device_kind():
    """The table is keyed by JAX's device_kind with the published v5e
    peaks; a kind it lacks raises instead of borrowing another chip's."""
    from repro.kernels import hw_constants as HW

    v5e = HW.chip("TPU v5 lite")
    assert v5e is HW.V5E
    assert v5e.peak_int8_ops == 393e12 and v5e.peak_bf16_flops == 197e12
    assert v5e.hbm_bw == 819e9
    assert v5e.ici_links * v5e.ici_bw * 8 == 1600e9     # 1,600 Gbit/s
    with pytest.raises(ValueError, match="TPU v9"):
        HW.chip("TPU v9")


def test_ops_prefill_autotune_reads_head_major_pool(monkeypatch):
    """The prefill q-block is priced with the pool's page size and kv-head
    count read off the head-major (n_pages, Hkv, P, D) pool."""
    import jax.numpy as jnp

    from repro.kernels import ops

    seen = {}

    def fake(sq, **kw):
        seen.update(kw, sq=sq)
        return 8

    monkeypatch.setattr(autotune, "prefill_bq", fake)
    q = jnp.zeros((1, 4, 16, 32), jnp.int8)          # (B, H, S, D)
    pool = jnp.zeros((5, 2, 8, 32), jnp.int8)        # (n_pages, Hkv, P, D)
    btab = jnp.zeros((1, 3), jnp.int32)
    assert ops._autotuned_bq(q, pool, btab, kv_bits=8) == 8
    assert seen == dict(sq=16, batch_slots=1, page_size=8, hkv=2, hd=32,
                        kv_bits=8, n_blocks=3, n_heads=4)

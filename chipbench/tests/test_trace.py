"""The trace reduction on a synthetic trace."""
import pytest

from chipbench import trace as T

MS = 1_000_000          # ns


def test_base_name_reads_hlo_and_module_names():
    assert T.base_name("jit_decode_step(17901282312488930036)") == \
        "jit_decode_step"
    assert T.base_name("%int4_matmul.7 = s8[64,4096]{1,0} custom-call()") \
        == "int4_matmul"
    assert T.base_name("%copy-done.28 = s32[36]{0} copy-done()") == \
        "copy-done"
    assert T.shaped_name("%fusion.108 = s8[622592]{0:T(1024)} fusion()") \
        == "fusion s8[622592]"


def test_union_merges_overlaps():
    assert T.union([(5, 9), (0, 2), (1, 3), (8, 12)]) == [(0, 3), (5, 12)]


def test_reduce_busy_idle_and_module_sums():
    # window 0..100 ms; ops overlap (a while op encloses two kernels)
    ops = {"/device:TPU:0": [
        ("%while.5 = (s32[]) while()", 10 * MS, 40 * MS),
        ("%int4_matmul.1 = s8[64,4096]{1,0} custom-call()", 12 * MS,
         20 * MS),
        ("%paged_decode_qattention.3 = s8[64]{0} custom-call()", 20 * MS,
         38 * MS),
        ("%int4_matmul.2 = s8[64,4096]{1,0} custom-call()", 60 * MS,
         70 * MS),
        ("%fusion.9 = s8[10]{0} fusion()", 65 * MS, 75 * MS),
        ("%fusion.9 = s8[10]{0} fusion()", 95 * MS, 120 * MS),   # clipped
    ]}
    mods = {"/device:TPU:0": [("jit_decode_step(1)", 10 * MS, 40 * MS),
                              ("jit_prefill(2)", 60 * MS, 75 * MS),
                              ("jit_prefill(3)", 200 * MS, 210 * MS)]}
    spans = [("chipbench.window", 0, 100 * MS),
             ("chipbench.poll", 0, 50 * MS),
             ("chipbench.record", 40 * MS, 50 * MS),
             ("chipbench.submit", 50 * MS, 60 * MS)]
    r = T.reduce_events(ops, mods, spans)
    assert r["window_s"] == pytest.approx(0.100)
    # busy: 10-40, 60-75, 95-100 -> 30 + 15 + 5 ms
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["ops"]["int4_matmul"] == {"s": pytest.approx(0.018), "n": 2}
    assert r["ops"]["paged_decode_qattention"]["s"] == pytest.approx(0.018)
    assert r["ops"]["fusion"]["s"] == pytest.approx(0.015)
    assert "while" not in r["ops"]
    assert r["modules"]["jit_prefill"] == {"s": pytest.approx(0.015), "n": 1}
    assert r["modules"]["jit_decode_step"]["n"] == 1
    # idle: 0-10 (poll), 40-60 (midpoint 50: submit, innermost), 75-95
    # (outside harness spans)
    idle = r["idle"]
    assert idle["chipbench.poll"] == pytest.approx(0.010)
    assert idle["chipbench.submit"] == pytest.approx(0.020)
    assert idle["outside harness spans"] == pytest.approx(0.020)
    assert sum(idle.values()) == pytest.approx(0.100 - r["busy_s"])


def test_reduce_averages_over_chips():
    one = [("%int4_matmul.1 = s8[8]{0} custom-call()", 0, 50 * MS)]
    ops = {"/device:TPU:0": one, "/device:TPU:1": []}
    r = T.reduce_events(ops, {}, [("chipbench.window", 0, 100 * MS)])
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx(0.025)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        T.reduce_events({}, {}, [("chipbench.poll", 0, 1)])

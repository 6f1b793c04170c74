"""The reduction of the program's spans and scopes on a synthetic trace."""
import json
from pathlib import Path

import pytest

from chipbench import peaks as P
from chipbench import run as RUN
from chipbench import spans as S
from chipbench import trace as T
from chipbench import work as W

MS = 1_000_000          # ns
ROOT = Path(__file__).resolve().parents[2]
DEC, PRE = "jit_decode_step(11)", "jit_prefill(22)"

# two ticks in a 100 ms window; each: admit, one chunk (prefill program
# 12-20 ms), decode dispatch, fetch while the decode program runs, pick
OPS = {"/device:TPU:0": [
    ("%fusion.3 = s8[128,64]{1,0} fusion()", 12 * MS, 16 * MS),
    ("%paged_prefill_qattention.1 = s8[1,8]{1,0} custom-call()",
     16 * MS, 20 * MS),
    ("%while.2 = (s32[]) while()", 22 * MS, 40 * MS),
    ("%int4_matmul.7 = s8[64,4096]{1,0} custom-call()", 22 * MS, 30 * MS),
    ("%fusion.3 = s8[64,64]{1,0} fusion()", 30 * MS, 34 * MS),
    ("%paged_decode_qattention.5 = s8[64]{0} custom-call()", 34 * MS,
     40 * MS),
    ("%fusion.3 = s8[64,64]{1,0} fusion()", 62 * MS, 66 * MS),
    ("%copy.9 = s8[8,16]{1,0} copy()", 66 * MS, 70 * MS),
]}
MODS = {"/device:TPU:0": [(PRE, 12 * MS, 20 * MS), (DEC, 22 * MS, 40 * MS),
                          (DEC, 62 * MS, 70 * MS)]}
OP_NAMES = {
    PRE: {"fusion.3": "jit(prefill)/while/body/mlp/act_lut/jit(_take)/gather",
          "paged_prefill_qattention.1": "jit(prefill)/while/body/attn/core/"
                                        "paged_prefill_qattention"},
    DEC: {"int4_matmul.7": "jit(decode_step)/while/body/mlp/up_gate/"
                           "int4_matmul",
          "fusion.3": "jit(decode_step)/while/body/mlp/act_lut/gather",
          "paged_decode_qattention.5": "jit(decode_step)/while/body/attn/"
                                       "core/paged_decode_qattention"},
}
HARNESS = [("chipbench.window", 0, 100 * MS),
           ("chipbench.poll", 0, 45 * MS), ("chipbench.poll", 50 * MS,
                                             75 * MS),
           ("chipbench.record", 45 * MS, 50 * MS)]
PROGRAM = [("serve.tick", 1 * MS, 44 * MS), ("serve.admit", 1 * MS, 3 * MS),
           ("serve.chunk", 3 * MS, 21 * MS),
           ("serve.fetch", 11 * MS, 21 * MS),
           ("serve.decode", 21 * MS, 22 * MS),
           ("serve.fetch", 22 * MS, 41 * MS), ("serve.pick", 41 * MS,
                                               44 * MS),
           ("serve.tick", 51 * MS, 74 * MS),
           ("serve.decode", 51 * MS, 62 * MS),
           ("serve.fetch", 62 * MS, 71 * MS), ("serve.pick", 71 * MS,
                                               74 * MS)]


def reduced(with_program=True):
    spans = HARNESS + (PROGRAM if with_program else [])
    return S.reduce_events(OPS, MODS, spans, OP_NAMES)


def test_gaps_are_split_among_the_innermost_spans():
    idle = reduced()["idle"]
    # gaps 0-12, 20-22, 40-62 and 70-100 ms, cut at span edges: 0-1 poll,
    # 1-3 admit, 3-11 chunk, 11-12 fetch | 20-21 fetch, 21-22 decode |
    # 40-41 fetch, 41-44 pick, 44-45 poll, 45-50 record, 50-51 poll, 51-62
    # decode | 70-71 fetch, 71-74 pick, 74-75 poll, 75-100 no span
    assert idle == {"chipbench.poll": pytest.approx(0.004),
                    "chipbench.record": pytest.approx(0.005),
                    "serve.admit": pytest.approx(0.002),
                    "serve.chunk": pytest.approx(0.008),
                    "serve.fetch": pytest.approx(0.004),
                    "serve.decode": pytest.approx(0.012),
                    "serve.pick": pytest.approx(0.006),
                    "outside harness spans": pytest.approx(0.025)}


def test_harness_keys_are_unchanged_by_program_spans():
    base = T.reduce_events(OPS, MODS, HARNESS)
    for r in (reduced(True), reduced(False)):
        for k in ("window_s", "busy_s", "chips", "ops", "ops_shaped",
                  "modules"):
            assert r[k] == base[k], k
        assert sum(r["idle"].values()) == pytest.approx(
            sum(base["idle"].values()))
    # without program spans only the harness's labels remain
    assert all(k.startswith("chipbench.") or k == "outside harness spans"
               for k in reduced(False)["idle"])


def test_accepted_metrics_read_the_same_from_both_reductions():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = json.loads((ROOT / "chipbench/configs/qwen3-4b.json").read_text())
    fwd = [{"kind": "decode", "contexts": [300] * 64},
           {"kind": "prefill", "pos0": 0, "ntok": 128, "final": True}]

    def rec(tr):
        return {"trace": tr, "counters": {"decode_steps": 2,
                                          "decode_tokens": 120},
                "slots": 64, "peaks": P.V5E, "window_s": 0.1,
                "work": W.window_work(m["model"], fwd, P.V5E)}

    before = rec(T.reduce_events(OPS, MODS, HARNESS))
    after = rec(reduced())
    for metric in bench["per_layer"]:
        read = RUN.load_reader(metric["name"])
        assert read(after) == read(before), metric["name"]


def test_ticks_spans_and_scopes():
    r = reduced()
    assert r["ticks"] == 2
    assert r["program_spans"]["serve.fetch"] == {
        "n": 3, "s": pytest.approx(0.038)}
    # the while op encloses the decode kernels and counts toward no scope;
    # the copy has no op_name; fusion.3 is the LUT gather in both programs
    assert r["scopes"] == {"mlp/act_lut": pytest.approx(0.012),
                           "mlp": pytest.approx(0.020),
                           "mlp/up_gate": pytest.approx(0.008),
                           "attn": pytest.approx(0.010),
                           "attn/core": pytest.approx(0.010)}
    assert r["ops_by_stack"]["copy s8[8,16] @ "] == pytest.approx(0.004)
    assert r["ops_by_stack"][
        "fusion s8[64,64] @ while/body/mlp/act_lut/gather"] == \
        pytest.approx(0.008)


def test_per_tick_readers():
    rec = {"trace": reduced()}
    read = {n: RUN.load_reader(n) for n in S.TICK_METRICS}
    # idle under serve.*: 2 + 8 + 4 + 12 + 6 ms over 2 ticks
    assert read["host_stall_ms_per_tick.tput"](rec) == pytest.approx(16.0)
    assert read["prefill_ms_per_tick.tput"](rec) == pytest.approx(4.0)
    assert read["act_lut_ms_per_tick.tput"](rec) == pytest.approx(6.0)
    # the harness's own reduction, or a program without spans: no reading
    for r in ({"trace": T.reduce_events(OPS, MODS, HARNESS)},
              {"trace": reduced(False)}, {"trace": None}):
        for name, reader in read.items():
            assert reader(r) is None, name


def _msg(*fields):
    """Protobuf wire bytes of (field number, int | bytes | str) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_come_from_the_metadata_planes_hlo_protos():
    ins = [_msg((1, "fusion.3"), (2, "fusion"),
                (7, _msg((1, "gather"), (2, "jit(f)/mlp/act_lut/gather")))),
           _msg((1, "copy.1"), (2, "copy"))]           # no metadata
    hlo = _msg((1, _msg((1, "jit_f"), (3, _msg((1, "main"), *[(2, i)
                                                               for i in ins])))))
    meta = _msg((1, 42), (2, "jit_f(42)"), (5, _msg((1, 7), (6, hlo))))
    plane = _msg((1, 3), (2, "/host:metadata"),
                 (4, _msg((1, 42), (2, meta))),
                 (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))))
    other = _msg((1, 1), (2, "/device:TPU:0"))
    space = _msg((1, other), (1, plane))
    assert S.module_op_names(space) == {
        "jit_f(42)": {"fusion.3": "jit(f)/mlp/act_lut/gather"}}

"""work.py against operations and bytes counted by hand."""
import json
from pathlib import Path

import pytest

from chipbench import peaks as P
from chipbench import work as W

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_qwen3_decode_step():
    m = model("qwen3-4b")
    # per layer: wq 2560x4096, wk/wv 2560x1024, wo 4096x2560,
    # gate/up 2560x9728, down 9728x2560 -> sum k*n = 100,925,440,
    # sum (k+n) = 57,344
    ops, nbytes = W.matmul_cost(m, 2)
    assert ops == 36 * 2 * 2 * 100_925_440 == 14_533_263_360
    assert nbytes == 36 * (100_925_440 / 2 + 2 * 57_344) == 1_820_786_688
    # attention over 100 + 200 live keys: 4 * 32 heads * 128 per key pair;
    # K and V of 8 heads * 128 bytes per key, q in and context out
    ops, nbytes = W.decode_attn_cost(m, [100, 200])
    assert ops == 36 * 4 * 32 * 128 * 300 == 176_947_200
    assert nbytes == 36 * (2 * 8 * 128 * 300 + 2 * 2 * 32 * 128) == 22_708_224
    assert W.head_ops(m, 2) == 2 * 2 * 2560 * 151_936


def test_stablelm_prefill_chunk():
    m = model("stablelm-1.6b")
    # per layer: q/k/v/o 2048x2048 (32 KV heads of 64), gate/up 2048x5632,
    # down 5632x2048 -> sum k*n = 51,380,224, sum (k+n) = 39,424
    ops, nbytes = W.matmul_cost(m, 128)
    assert ops == 24 * 2 * 128 * 51_380_224 == 315_680_096_256
    assert nbytes == 24 * (51_380_224 / 2 + 128 * 39_424) == 737_673_216
    # rows 128..255 over keys 0..row: 128*128 + 128*129/2 = 24,640 pairs
    ops, nbytes = W.prefill_attn_cost(m, 128, 128)
    assert ops == 24 * 4 * 32 * 64 * 24_640 == 4_844_421_120
    assert nbytes == 24 * (2 * 32 * 64 * 256 + 2 * 128 * 32 * 64) == 37_748_736


def test_window_work_least_time_and_step_ops():
    m = model("qwen3-4b")
    fw = [{"kind": "decode", "contexts": [100, 200]},
          {"kind": "prefill", "pos0": 0, "ntok": 128, "final": False},
          {"kind": "prefill", "pos0": 128, "ntok": 40, "final": True},
          {"kind": "decode", "contexts": []}]
    w = W.window_work(m, fw, P.V5E)
    mm2 = W.matmul_cost(m, 2)
    # two decode rows stream every weight: bound by bytes
    assert mm2[1] / 819e9 > mm2[0] / 393e12
    least = mm2[1] / 819e9
    for rows in (128, 40):
        o, b = W.matmul_cost(m, rows)
        least += max(o / 393e12, b / 819e9)
    assert w["int4_matmul"].least_s == pytest.approx(least)
    a1 = W.prefill_attn_cost(m, 0, 128)[0]
    a2 = W.prefill_attn_cost(m, 128, 40)[0]
    step = (mm2[0] + W.matmul_cost(m, 128)[0] + W.matmul_cost(m, 40)[0]
            + W.decode_attn_cost(m, [100, 200])[0] + a1 + a2
            + W.head_ops(m, 2) + W.head_ops(m, 1))
    assert w["step"].ops == pytest.approx(step)
    assert w["paged_decode_attn"].ops == W.decode_attn_cost(m, [100, 200])[0]


def test_unknown_device_kind_is_an_error():
    assert P.peaks_for("TPU v5 lite") is P.V5E
    with pytest.raises(KeyError):
        P.peaks_for("TPU v9000")

"""The harness without a chip: refusal on the CPU, metrics found by name,
the traffic generator, the spec, and one whole run at smoke size."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import run as RUN
from chipbench import traffic as TR

ROOT = Path(__file__).resolve().parents[2]


def test_refuses_a_cpu_backend_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_a_metric_reader_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "dummy_share.tput.py").write_text(
        "def read(rec):\n    return 2.0 * rec['x']\n")
    assert RUN.load_reader("dummy_share.tput", tmp_path)({"x": 21}) == 42.0


def test_every_metric_and_cell_of_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(RUN.load_reader(m["name"]))
    for w in bench["workloads"]:
        spec = RUN.load_spec(w["name"])
        e = spec["cell"]["engine"]
        assert e["chunk"] % e["page"] == 0
        assert spec["mix"]["prompt"]["hi"] + spec["mix"]["output"]["hi"] \
            <= e["max_len"]


def test_traffic_offers_every_seed_the_same_work():
    mix = TR.load_mix("chat")
    a = TR.Traffic(mix, 3, 1000)
    b = TR.Traffic(mix, 2**31 + 9, 1000)
    assert np.array_equal(a.due, b.due)
    x, y = a.due_until(60.0), b.due_until(60.0)
    assert [len(i.prompt) for i in x] == [len(i.prompt) for i in y]
    assert [i.max_new for i in x] == [i.max_new for i in y]
    assert not np.array_equal(x[0].prompt, y[0].prompt)
    dec = TR.load_mix("decode")
    a, b = TR.Traffic(dec, 3, 1000), TR.Traffic(dec, 4, 1000)
    wa, wb = a.first_wave(), b.first_wave()
    assert [i.max_new for i in wa] == [i.max_new for i in wb]
    assert max(i.max_new for i in wa) <= dec["output"]["hi"]
    assert min(i.max_new for i in wa) >= 1
    assert len(a.next_for(0).prompt) == len(b.next_for(5).prompt)


def test_chunk_shapes():
    assert TR.chunk_shapes(20, 70, 32, 16) == [16, 32]
    assert TR.chunk_shapes(128, 512, 128, 16) == list(range(16, 129, 16))
    assert TR.chunk_shapes(17, 17, 128, 16) == [32]


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs here write no persistent compile cache."""
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: "off")


def test_a_smoke_run_is_correct_and_reports_its_metrics(spec_of,
                                                        no_compile_cache):
    import jax
    res = RUN.run(spec_of("open"), 2**31 + 77, 2.0, False, jax.devices()[:1])
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert {"ttft_p50_ms", "ttft_p95_ms", "setup_s"} <= set(res["metrics"])
    assert res["attempted"] > 0 and res["failed"] == 0

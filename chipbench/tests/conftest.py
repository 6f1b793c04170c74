"""Helpers for the benchmark's CPU tests: a smoke-size spec built from the
data files beside this one."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

E2E = [{"name": n, "unit": u} for n, u in
       (("tokens_per_s", "tokens/s"), ("ttft_p50_ms", "ms"),
        ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"), ("setup_s", "s"))]


def smoke_spec(mix: str) -> dict:
    from chipbench import run as RUN

    data = HERE / "data"
    config = json.loads((data / "smoke.config.json").read_text())
    return {
        "workload": {"name": f"smoke.{mix}", "chips": 1},
        "config": config,
        "arch": RUN.load_arch(config, data / "smoke.config.json"),
        "mix": json.loads((data / f"smoke.{mix}.json").read_text()),
        "cell": json.loads((data / "smoke.cell.json").read_text()),
        "end_to_end": E2E,
        "per_layer": [],
    }


@pytest.fixture
def spec_of():
    return smoke_spec

"""Architecture modules: ``arch/dense.py`` computes what the harness computed
before the modules existed, a module is found by the configuration's name
for it, and a configuration that names none is refused."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model as M
from chipbench import reference as R
from chipbench import run as RUN

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DATA = HERE / "data"
FUNCTIONS = ("program_config", "folded", "score", "window_work")


def _sums(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(np.asarray(v, np.float64).sum())
            for k, v in flat}


@pytest.fixture(scope="module")
def smoke():
    config = json.loads((DATA / "smoke.config.json").read_text())
    pinned = json.loads((DATA / "dense.fingerprint.json").read_text())
    return (RUN.load_arch(config, DATA / "smoke.config.json"),
            config["model"], pinned)


def test_dense_weights_are_the_pinned_ones(smoke):
    # bfloat16 leaves summed in float64: exact on any host
    _, m, pinned = smoke
    assert _sums(M.make_params(m, pinned["seed"])) == pinned["params"]


def test_dense_calibration_ranges_are_the_pinned_ones(smoke):
    _, m, pinned = smoke
    seed = pinned["seed"]
    amax = R.calibrate(M.make_params(m, seed), R.model_items(m),
                       jnp.asarray(M.calib_tokens(m, seed)), 8)
    got = {jax.tree_util.keystr(k): np.asarray(v, np.float64).tolist()
           for k, v in jax.tree_util.tree_flatten_with_path(amax)[0]}
    assert got.keys() == pinned["amax"].keys()
    for k, v in pinned["amax"].items():
        # float32 reductions: the order XLA's CPU backend picks may differ
        # between hosts in the last bit, never more
        assert got[k] == pytest.approx(v, rel=1e-6, abs=0), k


def test_dense_folded_parameters_are_the_pinned_ones(smoke):
    arch, m, pinned = smoke
    got = _sums(arch.folded(arch.program_config(m), m, pinned["seed"]))
    assert got.keys() == pinned["folded"].keys()
    for k, v in pinned["folded"].items():
        assert got[k] == pytest.approx(v, rel=1e-6, abs=0), k


def test_dense_reference_gaps_are_the_pinned_ones(smoke):
    arch, m, pinned = smoke
    rng = np.random.default_rng(3)
    req = {"prompt": rng.integers(0, m["vocab_size"], 40).astype(np.int32),
           "out": rng.integers(0, m["vocab_size"], 12).astype(np.int32)}
    got = arch.score(m, pinned["seed"], [req], 96, control=True)
    for side in ("program", "control"):
        want = pinned["score"][side]
        assert got[side]["tokens"] == want["tokens"] == 12
        for k in ("mean", "widest", "not_first"):
            assert got[side][k] == pytest.approx(want[k], rel=1e-6, abs=0)


def _root(tmp_path: Path, arch: str = None) -> Path:
    """A checkout holding one smoke cell, ``toy.closed``, whose
    configuration names ``arch`` (no key where None)."""
    bench = tmp_path / "chipbench"
    for sub in ("configs", "cells", "traffic", "arch"):
        (bench / sub).mkdir(parents=True)
    config = json.loads((DATA / "smoke.config.json").read_text())
    del config["arch"]
    if arch is not None:
        config["arch"] = arch
    (bench / "configs" / "toy.json").write_text(json.dumps(config))
    shutil.copy(DATA / "smoke.cell.json", bench / "cells" / "toy.closed.json")
    shutil.copy(DATA / "smoke.closed.json", bench / "traffic" / "closed.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.closed", "config": "toy",
                       "traffic": "closed", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return tmp_path


TOY = '''"""dense, counting each call."""
from chipbench.arch import dense

CALLS = []


def _counted(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(dense, name)(*args, **kwargs)
    return call


program_config = _counted("program_config")
folded = _counted("folded")
score = _counted("score")
window_work = _counted("window_work")
'''


@pytest.fixture
def no_compile_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: "off")


def test_a_module_found_by_name_runs_a_cell_to_correct(tmp_path,
                                                       no_compile_cache):
    root = _root(tmp_path, "toy")
    (root / "chipbench" / "arch" / "toy.py").write_text(TOY)
    spec = RUN.load_spec("toy.closed", root=root)
    assert spec["arch"].__file__ == str(root / "chipbench/arch/toy.py")
    res = RUN.run(spec, 2**31 + 21, 2.0, False, jax.devices()[:1])
    assert res["correct"], res["compared"]
    assert res["compared"]["served_tokens_scored"]["value"] >= 10
    assert sorted(set(spec["arch"].CALLS)) == sorted(FUNCTIONS)


def test_a_configuration_without_arch_names_its_file(tmp_path):
    root = _root(tmp_path)
    with pytest.raises(KeyError, match="chipbench/configs/toy.json"):
        RUN.load_spec("toy.closed", root=root)


def test_every_cell_of_the_benchmark_resolves_its_module():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = RUN.load_spec(w["name"])
        for name in FUNCTIONS:
            assert callable(getattr(spec["arch"], name)), (w["name"], name)
        cfg = spec["arch"].program_config(spec["config"]["model"])
        assert cfg.n_layers == spec["config"]["model"]["n_layers"]

"""Architecture module ``dense``: a pre-norm decoder of grouped-query
attention (optional q/k RMS-norm, rotary on a leading fraction of each
head) and a SwiGLU MLP, RMSNorm or LayerNorm.

A configuration names its architecture by ``"arch"``; the harness loads
``chipbench/arch/<arch>.py`` by path and calls only its four functions,
each given the configuration's ``model`` block ``m``:

* ``program_config(m)``: the program's ``ModelConfig``, checked against
  the file;
* ``folded(cfg, m, seed)``: the program's set-up (weights from the seed,
  calibration, integer fold), returned on the device with the float
  weights freed; the harness never holds float weights;
* ``score(m, seed, sample, length, control=False)``: the plain reference
  over the sampled requests (``check.gap_stats`` under ``program``, and
  under ``control`` with ``control``), from its own weights and ranges;
* ``window_work(m, forwards, peaks)``: ``{family: Cost}`` with ``step``
  and every kernel family a reader names.

Here they bind ``model.py``, ``reference.py`` and ``work.py``.
"""
from __future__ import annotations

import sys
import time

from chipbench import check as C
from chipbench import model as M
from chipbench import reference as R
from chipbench import work as W


def program_config(m: dict):
    return M.program_config(m)


def folded(cfg, m: dict, seed: int):
    import jax

    t = time.perf_counter()
    params = M.make_params(m, seed)
    jax.block_until_ready(params)
    print(f"setup: weights {time.perf_counter() - t:.2f} s", file=sys.stderr,
          flush=True)
    t = time.perf_counter()
    out = M.fold_on_device(cfg, params, M.calib_tokens(m, seed))
    jax.block_until_ready(out)
    del params
    print(f"setup: calibration + fold {time.perf_counter() - t:.2f} s",
          file=sys.stderr, flush=True)
    return out


def score(m: dict, seed: int, sample: list, length: int,
          control: bool = False) -> dict:
    import jax.numpy as jnp

    params = M.make_params(m, seed)
    items = R.model_items(m)
    calib = jnp.asarray(M.calib_tokens(m, seed))
    amax = R.calibrate(params, items, calib, 8)
    ctrl = R.calibrate(params, items, calib, 4) if control else None

    def score_one(tokens, targets):
        return R.score(params, items, tokens, targets, amax, 8, ctrl)

    return C.score_requests(sample, length, R.QBLOCK, score_one, control)


def window_work(m: dict, forwards, peaks) -> dict:
    return W.window_work(m, forwards, peaks)

"""Serving launcher: QAT-calibrate (1 step), fold to integers, run batched
generation through the engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --prompts 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import fold as F
from repro.models import transformer as T
from repro.serve.engine import EngineConfig, Request, make_engine


def calibrated_folded(cfg, key, calib_tokens):
    """Seeded float params -> one calibration forward -> integer fold.

    Each phase is one jitted program, so XLA fuses the fold's float
    temporaries instead of materializing an f32 copy of every stacked
    weight (which would not fit one 16 GB chip at 4B parameters).  The
    float params are local: they are freed when this returns, before any
    engine is built, and only the folded integer tree stays on the device."""
    params = jax.jit(T.init_params, static_argnums=0)(cfg, key)
    amax = T.init_amax(cfg)
    _, obs, _ = jax.jit(T.forward, static_argnums=0)(cfg, params, amax,
                                                      calib_tokens)
    return jax.jit(F.fold_params, static_argnums=0)(cfg, params, obs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    configure_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(0)
    calib = jax.random.randint(key, (2, 32), 0, cfg.vocab_size)
    folded = calibrated_folded(cfg, key, calib)
    eng = make_engine(cfg, folded, EngineConfig(batch_slots=args.prompts,
                                                max_len=args.max_len))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (args.prompt_len,)).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for _ in range(args.prompts)]
    t0 = time.time()
    out = eng.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.out) for r in out)
    print(f"generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s batch={args.prompts})")
    for i, r in enumerate(out[:2]):
        print(f"req{i}: {r.out[:12].tolist()}...")


if __name__ == "__main__":
    main()

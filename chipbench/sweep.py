#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains (not part of a
run; its result is the rate the mix file fixes).

One engine is built and warmed as a run builds it; then for each rate, in
ascending order, arrivals run for the mix's warm-up and a window of
``--seconds``.  Printed per rate: requests due in the window, how many had
not left the waiting queue when it closed, TTFT p50/p95 from the due time,
and the waiting queue's length at the window's start and end.  The knee is
the highest rate whose waiting queue does not grow over the window.

    python3 chipbench/sweep.py --workload qwen3-4b.chat --seed 5 \
        --seconds 45 --rates 0.1,0.15,0.2
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as RUN  # noqa: E402
from chipbench import traffic as TR  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--warm", type=float, default=None,
                    help="seconds of arrivals before each window "
                         "(default: the mix's warm_s)")
    args = ap.parse_args(argv)
    spec = RUN.load_spec(args.workload)

    import contextlib

    import jax

    from repro.launch.compile_cache import configure_compile_cache
    if jax.devices()[0].platform != "tpu":
        RUN.log("sweep: needs a TPU")
        return 2
    configure_compile_cache()
    clock = time.perf_counter
    m = spec["config"]["model"]
    eng, _ = RUN.build_engine(spec, args.seed, jax.devices()[:1])
    RUN.warm(eng, spec, m["vocab_size"], clock, args.seed)
    drv = RUN.Driver(eng, spec["cell"]["engine"]["chunk"],
                     lambda _name: contextlib.nullcontext())
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(spec["mix"], rate=rate)
        if args.warm is not None:
            mix["warm_s"] = args.warm
        tr = TR.Traffic(mix, args.seed, m["vocab_size"])
        seen = {}

        def on():
            seen["q0"] = eng.stats()["waiting"]

        def off():
            seen["q1"] = eng.stats()["waiting"]
            seen["t1"] = clock()

        w0, w1, due_in = RUN.open_loop(drv, tr, clock, mix["warm_s"],
                                       args.seconds, on, off)
        ttft = [1e3 * (r.times[0] - r.due) for r in due_in if r.times]
        waiting = sum(1 for r in due_in
                      if r.admit_t is None or r.admit_t > seen["t1"])
        print(json.dumps({
            "rate": rate, "due_in_window": len(due_in),
            "not_admitted_at_close": waiting,
            "waiting_start": seen["q0"], "waiting_end": seen["q1"],
            "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
            "window_s": w1 - w0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

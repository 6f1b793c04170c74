#!/usr/bin/env python3
"""Readings that the ``correct`` limits are set from (not part of a run).

For each seed, in one process that holds the chip: one run of the cell
at its own sizes and load (a short window), then the architecture
module's plain reference (``arch/<arch>.py`` ``score``) scores the same
sample a run scores, and its control (for ``dense``: the reference with
every activation site at int4) reads, at each position of the same
prompts and served tokens, the reference's gap of the token it ranks
first.  One JSON line per seed on standard output.

    python3 chipbench/control.py --workload qwen3-4b.decode \
        --seeds 11,12,13 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as RUN  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = RUN.load_spec(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        RUN.log("control: needs a TPU")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        run = RUN.measure(spec, seed, args.seconds, False, devs[:1])
        got = RUN.check_outputs(spec, seed, run, spec["cell"]["check"]["sample"],
                                control=True)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())

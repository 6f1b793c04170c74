"""The one traffic generator.  A mix is a data file
``chipbench/traffic/<mix>.json`` of parameters; this module reads it.

Sizes (prompt and output lengths, arrival times) come from a fixed
sequence drawn once from the mix's own ``sizes_seed``; ``--seed`` draws
only the token contents (and, in the harness, the weights).  Every seed
therefore offers the same work in the same order, so runs differ by the
system's noise and not by how much work the seed asked for.

Two loops:

* ``closed``: ``clients`` callers; each sends its next request when its
  last one completes.  The first wave's output lengths are residual lives
  (uniform from 1 to the drawn length), so completions are spread from the
  start instead of arriving in one wave.
* ``poisson``: independent users; requests are due at exponential gaps of
  mean ``1 / rate`` seconds, sent at their due time whatever the server
  does, and timed from it.

Length distributions: ``uniform`` (``lo``..``hi`` inclusive) or
``lognormal`` (``median``, ``sigma``, clipped to ``lo``..``hi``).  The
arithmetic follows ``benchmarks/serve_bench.py``'s generators, with
arrivals in seconds instead of engine ticks.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
POOL = 4096                 # sizes drawn per pool


def load_mix(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        return rng.integers(spec["lo"], spec["hi"] + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


@dataclasses.dataclass
class Item:
    """One request to send: ``due_s`` relative to the generator's start
    (open loop), or None for a closed-loop caller's next request."""
    prompt: np.ndarray
    max_new: int
    client: int
    due_s: Optional[float] = None


class Traffic:
    """The seeded request stream of one run."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        sizes = np.random.default_rng(mix["sizes_seed"])
        self.prompts = draw_lengths(sizes, mix["prompt"], POOL)
        self.outputs = draw_lengths(sizes, mix["output"], POOL)
        self.tokens = np.random.default_rng([seed, 3])
        self.next_i = 0
        self.kind = mix["kind"]
        if self.kind == "closed":
            c = self.clients = mix["clients"]
            # residual lives of the first wave
            resid = np.random.default_rng(mix["sizes_seed"] + 1)
            self.outputs[:c] = resid.integers(1, self.outputs[:c] + 1)
        elif self.kind == "poisson":
            self.rate = mix["rate"]
            self.due = np.cumsum(sizes.exponential(1.0 / self.rate, POOL))
        else:
            raise ValueError(f"unknown loop {self.kind!r}")

    def _draw(self, client: int, due: Optional[float]) -> Item:
        if self.next_i >= POOL:
            raise RuntimeError("traffic pool exhausted; raise POOL")
        j = self.next_i
        self.next_i += 1
        prompt = self.tokens.integers(0, self.vocab,
                                      int(self.prompts[j])).astype(np.int32)
        return Item(prompt, int(self.outputs[j]), client, due)

    # --- closed loop ------------------------------------------------------
    def first_wave(self) -> List[Item]:
        return [self._draw(c, None) for c in range(self.clients)]

    def next_for(self, client: int) -> Item:
        return self._draw(client, None)

    # --- open loop --------------------------------------------------------
    def peek_due(self) -> float:
        """When the next request is due (seconds from the start)."""
        return float(self.due[min(self.next_i, POOL - 1)])

    def due_until(self, t_s: float) -> List[Item]:
        """Every request due at or before ``t_s`` not yet handed out."""
        out = []
        while self.next_i < POOL and self.due[self.next_i] <= t_s:
            out.append(self._draw(-1, float(self.due[self.next_i])))
        return out


def chunk_shapes(lo: int, hi: int, chunk: int, page: int) -> List[int]:
    """The padded prefill chunk lengths prompts of lo..hi tokens produce
    under a ``chunk``-token per-slot chunk cap: every full chunk, and each
    ragged last chunk padded to whole pages."""
    shapes = set()
    for p in range(lo, hi + 1):
        if p > chunk:
            shapes.add(chunk)
        last = p - chunk * ((p - 1) // chunk)
        shapes.add(-(-last // page) * page)
    return sorted(shapes)


def prompt_range(mix: dict) -> tuple:
    """The smallest and largest prompt the mix can produce."""
    return mix["prompt"]["lo"], mix["prompt"]["hi"]

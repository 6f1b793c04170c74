"""The comparison that decides ``correct``.

After the window, a sample of the requests the timed engine finished,
drawn from the seed and always holding the longest, is scored by the plain
reference of the configuration's architecture (``arch/<arch>.py``
``score``) over each prompt with its served tokens.  The number compared is
the mean gap, over every served token of the sample, by which the served
token's reference logit lies below the reference's best logit at that
position.  Greedy serving of a correct W4A8 program keeps it small; a token
altered where it is produced, a wrong cache row, or int4 arithmetic (the
control) opens it.
"""
from __future__ import annotations

import numpy as np


def pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def sample_requests(done: list, n: int, seed: int) -> list:
    """``n`` finished requests: the one with the most served tokens, and the
    rest drawn from the seed.  ``done`` holds dicts with ``prompt`` and
    ``out``."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: (-len(done[i]["out"]), i))
    rest = order[1:]
    rng = np.random.default_rng([seed, 7])
    pick = list(rng.permutation(rest)[:max(n - 1, 0)])
    return [done[i] for i in [order[0], *pick]]


def score_requests(reqs: list, length: int, block: int, score_one,
                   control: bool = False) -> dict:
    """Gaps over every served token of ``reqs``: ``program`` (the served
    token's reference logit below the reference's best) and, with
    ``control``, ``control`` (the same for the token the control ranks
    first at that position).  Each request is teacher-forced as one
    sequence padded to ``length`` (the cell's ``max_len``) rounded up to
    ``block``, so one compiled reference serves the cell:
    ``score_one(tokens, targets)`` returns per position the reference's best
    logit, its logit of ``targets`` (the token served after the position)
    and its logit of the control's first choice."""
    import jax.numpy as jnp

    prog, ctrl_gaps = [], []
    for r in reqs:
        prompt = np.asarray(r["prompt"], np.int64)
        served = np.asarray(r["out"], np.int64)
        seq = np.concatenate([prompt, served[:-1]])
        n = len(seq)
        tokens = np.zeros(pad_to(max(n, length), block), np.int32)
        tokens[:n] = seq
        targets = np.zeros_like(tokens)
        first = len(prompt) - 1            # row that predicts served[0]
        targets[first:first + len(served)] = served
        best, got, ctrl = (np.asarray(x) for x in score_one(
            jnp.asarray(tokens), jnp.asarray(targets)))
        rows = slice(first, first + len(served))
        prog.append(best[rows] - got[rows])
        if control:
            ctrl_gaps.append(best[rows] - ctrl[rows])
    out = {"program": gap_stats(prog)}
    if control:
        out["control"] = gap_stats(ctrl_gaps)
    return out


def gap_stats(per_request: list) -> dict:
    """The numbers read from per-position gaps: their mean over every
    served token (the one compared), the widest, and the share of tokens
    that are not the reference's first choice."""
    if not per_request:
        return {"mean": float("inf"), "widest": float("inf"),
                "not_first": 1.0, "tokens": 0, "requests": 0}
    g = np.concatenate(per_request)
    return {"mean": float(np.mean(g)), "widest": float(np.max(g)),
            "not_first": float(np.mean(g > 0)), "tokens": int(g.size),
            "requests": len(per_request),
            "widest_per_request": [float(np.max(x)) for x in per_request]}

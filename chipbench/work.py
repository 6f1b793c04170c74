"""Operations and bytes each kernel family needs, from the model's sizes and
the rows and context lengths the harness counted in the window.

These are the work the algorithm requires, not what a kernel happens to
move: weights once per forward (int4: half a byte each), int8 activations
in and out, live KV rows only (never the padded table width), one lm_head
row per token the engine uses.  So a roofline share reads the same work
whatever implements it.

A forward is a dict: ``{"kind": "decode", "contexts": [L, ...]}`` (one
entry per active slot: keys attended after this step's write) or
``{"kind": "prefill", "pos0": p, "ntok": n, "final": bool}`` (one chunk).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable


@dataclasses.dataclass
class Cost:
    ops: float = 0.0
    bytes: float = 0.0
    least_s: float = 0.0      # sum over calls of max(ops/peak, bytes/bw)

    def add(self, ops: float, nbytes: float, peaks) -> None:
        self.ops += ops
        self.bytes += nbytes
        self.least_s += max(ops / peaks.int8_ops,
                            nbytes / peaks.hbm_bytes_per_s)


def linears(m: dict):
    """(in, out) of every int4 linear of one layer."""
    d, hd, nh = m["d_model"], m["head_dim"], m["n_heads"]
    nkv, ff = m["n_kv_heads"], m["d_ff"]
    return [(d, nh * hd), (d, nkv * hd), (d, nkv * hd), (nh * hd, d),
            (d, ff), (d, ff), (ff, d)]


def matmul_cost(m: dict, rows: int):
    """(ops, bytes) of one forward's int4 linears over ``rows`` tokens."""
    ops = nbytes = 0.0
    for k, n in linears(m):
        ops += 2.0 * rows * k * n
        nbytes += k * n / 2 + rows * (k + n)
    return m["n_layers"] * ops, m["n_layers"] * nbytes


def head_ops(m: dict, rows: int) -> float:
    return 2.0 * rows * m["d_model"] * m["vocab_size"]


def decode_attn_cost(m: dict, contexts: list):
    """(ops, bytes) of paged decode attention over the slots' live keys."""
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    keys = float(sum(contexts))
    ops = 4.0 * nh * hd * keys
    nbytes = 2.0 * nkv * hd * keys + 2.0 * len(contexts) * nh * hd
    return m["n_layers"] * ops, m["n_layers"] * nbytes


def prefill_attn_cost(m: dict, pos0: int, ntok: int):
    """(ops, bytes) of causal chunk attention: rows pos0..pos0+ntok-1 over
    keys 0..row, each key row read once."""
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pairs = ntok * pos0 + ntok * (ntok + 1) / 2.0
    ops = 4.0 * nh * hd * pairs
    nbytes = 2.0 * nkv * hd * (pos0 + ntok) + 2.0 * ntok * nh * hd
    return m["n_layers"] * ops, m["n_layers"] * nbytes


def window_work(m: dict, forwards: Iterable[dict], peaks) -> Dict[str, Cost]:
    """Per kernel family, and ``step`` for the whole model's needed
    operations (matmuls, used lm_head rows, attention)."""
    out = {k: Cost() for k in ("int4_matmul", "paged_decode_attn",
                               "paged_prefill_attn", "step")}
    for f in forwards:
        if f["kind"] == "decode":
            rows = len(f["contexts"])
            if not rows:
                continue
            a_ops, a_bytes = decode_attn_cost(m, f["contexts"])
            out["paged_decode_attn"].add(a_ops, a_bytes, peaks)
            head_rows = rows
        else:
            rows = f["ntok"]
            a_ops, a_bytes = prefill_attn_cost(m, f["pos0"], f["ntok"])
            out["paged_prefill_attn"].add(a_ops, a_bytes, peaks)
            head_rows = 1 if f["final"] else 0
        mm_ops, mm_bytes = matmul_cost(m, rows)
        out["int4_matmul"].add(mm_ops, mm_bytes, peaks)
        out["step"].ops += mm_ops + a_ops + head_ops(m, head_rows)
    return out

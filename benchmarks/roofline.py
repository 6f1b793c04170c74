"""Roofline analysis from the dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), TPU v5e constants
(``kernels/hw_constants.V5E``; the dry run compiles the bf16 step):

    compute    = dot_FLOPs        / (chips * 197e12 FLOP/s)
    memory     = hbm_bytes        / (chips * 819e9  B/s)
    collective = collective_bytes / (chips * 4 links * 50e9 B/s)

Inputs are the **loop-aware** costs stored by the dry-run
(``benchmarks/hlo_cost.py``: every while-body's costs scaled by its
``known_trip_count``), so scan-over-layers and grad-accumulation are fully
counted — unlike raw ``cost_analysis()``, which counts loop bodies once
(measured discrepancy ~100x on 32-layer models; see EXPERIMENTS.md §Roofline
notes).  dot_flops/collective bytes are exact per the partitioned HLO; the
HBM term uses CPU-backend fusion granularity and over-estimates TPU traffic
(fusion on TPU merges more elementwise chains) — treat it as an upper bound.

MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

# per-chip ceilings — single source of truth, drift-tested
from repro.kernels.hw_constants import V5E as CHIP  # noqa: E402
RESULTS = ROOT / "results" / "dryrun"
OUT = ROOT / "results" / "roofline"


def model_flops(cfg, shape) -> float:
    n_active = cfg.active_params_estimate()
    toks = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        return 6.0 * n_active * toks
    return 2.0 * n_active * toks


def analyze_record(rec: dict, cfg, shape):
    chips = rec["chips"]
    hc = rec.get("hlo_cost")
    if not hc:
        return None
    flops_dev = hc["dot_flops"]
    bytes_dev = hc["hbm_bytes"]
    coll_dev = hc["collective_bytes"]
    t_compute = flops_dev / CHIP.peak_bf16_flops
    t_memory = bytes_dev / CHIP.hbm_bw
    t_coll = coll_dev / (CHIP.ici_links * CHIP.ici_bw)
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    hlo_total = flops_dev * chips
    bound = max(t_compute, t_memory, t_coll)
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "mesh": "2x16x16" if rec["multi_pod"] else "16x16",
        "chips": chips,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_total,
        "useful_ratio": mf / hlo_total if hlo_total else 0.0,
        "roofline_bound_s": bound,
        "compute_fraction": t_compute / max(bound, 1e-30),
        "mfu_bound": mf / (chips * CHIP.peak_bf16_flops) / max(bound, 1e-30),
        "hbm_gb_per_dev": (rec["memory"]["temp_bytes"]
                           + rec["memory"]["argument_bytes"]) / 2**30,
        "collective_breakdown": {k: v["bytes"] for k, v in
                                 hc["collectives"].items()},
        "tag": rec.get("tag", ""),
    }


def load_all(pattern="*.json"):
    from repro.configs.base import SHAPES, get_config

    rows = []
    for f in sorted(RESULTS.glob(pattern)):
        try:
            rec = json.loads(f.read_text())
        except json.JSONDecodeError:
            continue
        if not rec.get("ok"):
            continue
        cfg = get_config(rec["arch"])
        r = analyze_record(rec, cfg, SHAPES[rec["shape"]])
        if r:
            rows.append(r)
    return rows


def main():
    OUT.mkdir(parents=True, exist_ok=True)
    rows = [r for r in load_all() if not r["tag"]]
    (OUT / "roofline.json").write_text(json.dumps(rows, indent=1))
    lines = ["| arch | shape | t_comp | t_mem* | t_coll | bound | MFU-bound |"
             " MODEL/HLO | HBM GB/dev |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != "16x16":
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.2e} | "
            f"{r['t_memory_s']:.2e} | {r['t_collective_s']:.2e} | "
            f"{r['dominant']} | {r['mfu_bound']:.3f} | "
            f"{r['useful_ratio']:.2f} | {r['hbm_gb_per_dev']:.1f} |")
    (OUT / "roofline.md").write_text("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()

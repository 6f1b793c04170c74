"""Reduction of the traced window's ``.xplane.pb`` to per-layer numbers.

* device busy time: the union of op intervals on each chip's op line,
  averaged over the chips the run used;
* per-program device time and calls, by XLA module name
  (``jit_decode_step``, ``jit_prefill``);
* per-op device time, by HLO instruction name with numeric suffixes
  removed (the Mosaic kernels appear as ``int4_matmul``,
  ``paged_decode_qattention``, ``paged_prefill_qattention``); ops that
  enclose others (``while``, ``conditional``, ``call``) count toward busy
  time only;
* idle gaps inside the window, each labelled by the innermost harness span
  (``chipbench.*`` host annotations) it falls in, summed per label.

Everything is read from the trace alone, on its own clock.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SUFFIX = re.compile(r"(\.\d+|\(\d+\))+$")
HLO_NAME = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)*\s*=")
CONTAINERS = {"while", "conditional", "call"}   # ops that enclose others
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"


def base_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``; an op event named by
    its HLO text (``%int4_matmul.7 = s8[...] custom-call(...)``) -> its
    instruction name without the numeric suffix (``int4_matmul``)."""
    m = HLO_NAME.match(name)
    return SUFFIX.sub("", m.group(1) if m else name)


def shaped_name(name: str) -> str:
    """The op's base name with its result type, layouts dropped
    (``fusion s8[64,9728]``): tells apart fusions that share a name."""
    base = base_name(name)
    rest = name.split("=", 1)[1].strip() if "=" in name else ""
    if rest.startswith("("):
        return base
    return f"{base} {re.sub(r'[{].*', '', rest.split(' ', 1)[0])}".strip()


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def label_gaps(busy, spans, lo, hi) -> Dict[str, float]:
    """Idle time in [lo, hi) outside ``busy`` (sorted disjoint), summed per
    label of the innermost span covering each gap's midpoint."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inner = [(se - ss, name) for name, ss, se in spans
                 if ss <= mid < se and name != WINDOW_SPAN]
        label = min(inner)[1] if inner else "outside harness spans"
        out[label] += (e - s) * 1e-9
    return dict(out)


def reduce_events(device_ops: Dict[str, list], device_modules: Dict[str, list],
                  spans: List[Tuple[str, float, float]]) -> dict:
    """``device_ops`` / ``device_modules``: per chip, a list of (name,
    start_ns, end_ns); ``spans``: host (name, start_ns, end_ns).  The window
    is the ``chipbench.window`` span."""
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = win[0]
    ops: Dict[str, dict] = defaultdict(lambda: {"s": 0.0, "n": 0})
    shaped: Dict[str, dict] = defaultdict(lambda: {"s": 0.0, "n": 0})
    mods: Dict[str, dict] = defaultdict(lambda: {"s": 0.0, "n": 0})
    busy_total, idle = 0.0, defaultdict(float)
    chips = sorted(device_ops)
    for chip in chips:
        iv = []
        for name, s, e in device_ops[chip]:
            if e <= lo or s >= hi:
                continue
            iv.append((s, e))
            op = base_name(name)
            if op in CONTAINERS:
                continue
            dt = (min(e, hi) - max(s, lo)) * 1e-9
            for rec in (ops[op], shaped[shaped_name(name)]):
                rec["s"] += dt
                rec["n"] += 1
        busy = union(clip(iv, lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for k, v in label_gaps(busy, spans, lo, hi).items():
            idle[k] += v / len(chips)
        for name, s, e in device_modules.get(chip, []):
            if e <= lo or s >= hi:
                continue
            rec = mods[base_name(name)]
            rec["s"] += (min(e, hi) - max(s, lo)) * 1e-9
            rec["n"] += 1
    n = max(len(chips), 1)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_total / n,
            "chips": len(chips),
            "ops": {k: {"s": v["s"] / n, "n": v["n"]} for k, v in ops.items()},
            "ops_shaped": {k: {"s": v["s"] / n, "n": v["n"]}
                           for k, v in shaped.items()},
            "modules": {k: {"s": v["s"] / n, "n": v["n"]}
                        for k, v in mods.items()},
            "idle": dict(idle)}


def read_xplane(path: str):
    """(device_ops, device_modules, spans) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_ops: Dict[str, list] = {}
    dev_mods: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                if line.name in OP_LINES:
                    dev_ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                elif line.name in MODULE_LINES:
                    dev_mods[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return dev_ops, dev_mods, spans


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_events(*read_xplane(paths[-1]))


def describe(trace_dir: str, limit: int = 12) -> str:
    """A plain listing of planes, lines and sample events (with stats), for
    looking at a trace by hand."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name}: "
                   f"{[(ln.name, sum(1 for _ in ln.events)) for ln in lines]}")
        for ln in lines:
            names = defaultdict(int)
            for e in ln.events:
                names[e.name] += 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            out.append(f"  LINE {ln.name}: {top}")
            for e in list(ln.events)[:2]:
                out.append(f"    e {e.name} {e.start_ns} {e.duration_ns} "
                           f"{[(s[0], str(s[1])[:80]) for s in e.stats][:8]}")
    return "\n".join(out)

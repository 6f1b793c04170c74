"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader takes the run record and returns a number, or None where the
record holds nothing to read (a kernel that did not run, a trace that was
not taken).  None is never turned into 0.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np


def kernel_seconds(rec: dict, pattern: str) -> float:
    """Device seconds of the trace's ops whose name matches ``pattern``."""
    tr = rec.get("trace")
    if not tr:
        return 0.0
    rx = re.compile(pattern)
    return sum(v["s"] for k, v in tr["ops"].items() if rx.search(k))


def roofline(rec: dict, family: str, pattern: str) -> Optional[float]:
    """Least time the window's ``family`` work needs on this chip, over the
    device time its kernels took, in percent."""
    t = kernel_seconds(rec, pattern)
    least = rec["work"][family].least_s
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t


def module_ms(rec: dict, module: str) -> Optional[float]:
    """Mean device milliseconds per call of one jitted program."""
    tr = rec.get("trace")
    if not tr or module not in tr["modules"]:
        return None
    v = tr["modules"][module]
    return 1e3 * v["s"] / v["n"] if v["n"] else None


def idle_share(rec: dict) -> Optional[float]:
    tr = rec.get("trace")
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_mfu(rec: dict) -> Optional[float]:
    """Model int8 operations needed for the window's tokens over the
    window's length at the chip's int8 peak, in percent (read in a traced
    run on the chip only)."""
    ops = rec["work"]["step"].ops
    tr = rec.get("trace")
    if not tr or not tr["chips"] or ops <= 0 or rec["window_s"] <= 0:
        return None
    return 100.0 * ops / (rec["window_s"] * rec["peaks"].int8_ops)


def p95(xs) -> Optional[float]:
    return float(np.percentile(xs, 95)) if len(xs) else None

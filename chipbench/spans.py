#!/usr/bin/env python3
"""The program's own spans and named scopes in a traced window.

``trace.py`` reduces a traced window with the harness's spans
(``chipbench.*``) alone.  The program marks its own phases too: the
serving engine opens ``jax.profiler.TraceAnnotation`` spans named in
``repro.serve.stats.SPANS`` (``serve.tick`` around each ``Engine.poll``,
``serve.chunk``, ``serve.fetch`` and the rest inside it), and the integer
forward puts its layers under ``jax.named_scope`` (``attn/core``,
``mlp/act_lut``, ...).  This module reads both from the same
``.xplane.pb`` and extends ``trace.reduce_events``' result:

* ``idle``: the same idle time, cut at span edges and summed per
  innermost span among the harness's and the program's (``serve.fetch``,
  ``serve.pick``, ...);
* ``ticks``: ``serve.tick`` spans that start in the window, and
  ``program_spans``: count and host seconds of each ``serve.*`` span;
* ``scopes``: device seconds per named scope (``SCOPES``; a scope counts
  the ops of the scopes nested in it), and ``ops_by_stack``: device
  seconds per op and name stack.

``busy_s``, ``window_s``, ``chips``, ``ops``, ``ops_shaped`` and
``modules`` are ``trace.reduce_events``' own, computed the same way.

A TPU op event carries no name stack: its name is the HLO instruction's
text.  The stack is the instruction's ``metadata.op_name`` in the
optimized HLO module that the profiler stores in the trace's
``/host:metadata`` plane; ops are matched to their module by the
``XLA Modules`` event they fall in on the same chip.  The plane's protos
are read from the file's protobuf wire format (XSpace and HloProto field
numbers), so nothing beyond JAX is needed.

By hand, one cell with the trace reduced here, the three per-tick readers
added, and no reference check:

    python3 chipbench/spans.py --workload qwen3-4b.decode --seed 5 \
        --seconds 51
"""
from __future__ import annotations

import bisect
import glob
import json
import re
import shutil
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
for _p in (str(HERE.parent / "src"), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace as T  # noqa: E402

SPAN_PREFIXES = ("chipbench.", "serve.")
TICK_SPAN = "serve.tick"
SCOPES = ("attn", "attn/qkv", "attn/core", "attn/kv_write", "mlp",
          "mlp/up_gate", "mlp/act_lut", "mlp/down", "norm", "lm_head")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
INSTR = re.compile(r"^%?([^\s=]+)\s*=")
# the per-tick readers in metrics/ that read what this module adds
TICK_METRICS = ("host_stall_ms_per_tick.tput", "prefill_ms_per_tick.tput",
                "act_lut_ms_per_tick.tput")


# --- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields (fixed-width fields skipped)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield num, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _first(buf, num: int, default=None):
    return next((v for k, v in fields(buf) if k == num), default)


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """{instruction name: metadata.op_name} of one serialized ``HloProto``
    (hlo_module 1 -> computations 3 -> instructions 2 -> name 1,
    metadata 7 -> op_name 2)."""
    out = {}
    module = _first(hlo_proto, 1, b"")
    for k, comp in fields(module):
        if k != 3:
            continue
        for k2, ins in fields(comp):
            if k2 != 2:
                continue
            name = op_name = None
            for k3, v in fields(ins):
                if k3 == 1:
                    name = bytes(v).decode()
                elif k3 == 7:
                    op = _first(v, 2)
                    op_name = bytes(op).decode() if op is not None else None
            if name and op_name:
                out[name] = op_name
    return out


def module_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program, named as its ``XLA Modules`` events name it
    (``jit_decode_step(<program id>)``): {instruction: op_name}}, from the
    ``Hlo Proto`` stats of the metadata plane's event metadata."""
    out: Dict[str, Dict[str, str]] = {}
    for k, plane in fields(xspace):
        if k != 1 or bytes(_first(plane, 2, b"")).decode() != METADATA_PLANE:
            continue
        stat_names, metas = {}, []
        for k2, v in fields(plane):
            if k2 == 5:                     # stat_metadata map entry
                meta = _first(v, 2, b"")
                stat_names[_first(meta, 1, 0)] = bytes(
                    _first(meta, 2, b"")).decode()
            elif k2 == 4:                   # event_metadata map entry
                metas.append(_first(v, 2, b""))
        for meta in metas:
            name = bytes(_first(meta, 2, b"")).decode()
            for k3, stat in fields(meta):
                if k3 == 5 and stat_names.get(_first(stat, 1, 0)) == \
                        HLO_PROTO_STAT:
                    out[name] = hlo_op_names(_first(stat, 6, b""))
    return out


# --- reading and reducing ----------------------------------------------------

def span_events(path: str, prefixes=SPAN_PREFIXES) -> List[tuple]:
    """Host spans as (name, start_ns, end_ns, {kwarg: value})."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {s[0]: s[1] for s in e.stats}))
    return out


def read_xplane(path: str):
    """(device_ops, device_modules, spans, op_names) from one ``.xplane.pb``;
    spans are the harness's and the program's."""
    dev_ops, dev_mods, _ = T.read_xplane(path)
    spans = [s[:3] for s in span_events(path)]
    return dev_ops, dev_mods, spans, module_op_names(Path(path).read_bytes())


def name_stack(op_name: str) -> str:
    """The op's name stack without transform and call segments."""
    return "/".join(p for p in op_name.split("/") if "(" not in p)


def in_scope(op_name: str, scope: str) -> bool:
    """``scope`` (``a/b``) is a run of whole segments of the op's name stack
    once transform and call segments (``jit(...)``, ``vmap(...)``) are
    dropped: ``jit(decode_step)/while/body/mlp/act_lut/jit(_take)/gather``
    is under ``mlp``, ``mlp/act_lut`` and ``act_lut``."""
    return f"/{scope}/" in f"/{name_stack(op_name)}/"


def scope_seconds(device_ops, device_modules, op_names, lo, hi):
    """Device seconds in [lo, hi), averaged over chips: per scope of
    ``SCOPES``, and per (op with its result type, name stack), the stack
    ``""`` where the op's module or instruction has none.  An op is looked
    up in the module whose ``XLA Modules`` event holds its start."""
    scopes: Dict[str, float] = defaultdict(float)
    by_op: Dict[Tuple[str, str], float] = defaultdict(float)
    seen: Dict[Tuple[str, str], tuple] = {}     # (module, op) -> its keys
    chips = sorted(device_ops)
    for chip in chips:
        mods = sorted((s, e, name) for name, s, e in
                      device_modules.get(chip, []))
        starts = [m[0] for m in mods]
        for name, s, e in device_ops[chip]:
            if e <= lo or s >= hi or T.base_name(name) in T.CONTAINERS:
                continue
            j = bisect.bisect_right(starts, s) - 1
            module = mods[j][2] if j >= 0 and s < mods[j][1] else ""
            keys = seen.get((module, name))
            if keys is None:
                m = INSTR.match(name)
                op_name = op_names.get(module, {}).get(m.group(1), "") \
                    if m else ""
                keys = seen[(module, name)] = (
                    (T.shaped_name(name), name_stack(op_name)),
                    [sc for sc in SCOPES if op_name and in_scope(op_name, sc)])
            dt = (min(e, hi) - max(s, lo)) * 1e-9
            by_op[keys[0]] += dt
            for scope in keys[1]:
                scopes[scope] += dt
    n = max(len(chips), 1)
    return ({k: v / n for k, v in scopes.items()},
            {k: v / n for k, v in by_op.items()})


def split_gaps(busy, spans, lo, hi) -> Dict[str, float]:
    """Idle time in [lo, hi) outside ``busy`` (sorted disjoint), cut at the
    spans' edges, each piece summed under the innermost span covering it
    (``trace.label_gaps`` gives a whole gap the label at its midpoint: a
    gap that runs from the end of a logits fetch through the token pick
    would count as fetch alone)."""
    inner = [sp for sp in spans if sp[0] != T.WINDOW_SPAN]
    edges = sorted({lo, hi} | {x for _, s, e in inner for x in (s, e)
                                if lo < x < hi})
    pieces = []
    for a, b in zip(edges, edges[1:]):
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in inner if s <= mid < e]
        pieces.append((a, b, min(cover)[1] if cover
                       else "outside harness spans"))
    out: Dict[str, float] = defaultdict(float)
    j, t = 0, lo
    for s, e in busy + [(hi, hi)]:
        if s > t:                                   # the gap [t, s)
            while pieces[j][1] <= t:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < s:
                a, b, label = pieces[k]
                out[label] += (min(b, s) - max(a, t)) * 1e-9
                k += 1
        t = max(t, e)
    return dict(out)


def reduce_events(device_ops, device_modules, spans, op_names) -> dict:
    """``trace.reduce_events`` with ``idle`` split at span edges among the
    harness's and the program's spans (``split_gaps``), plus ``ticks``,
    ``scopes``, ``ops_by_stack`` (device seconds per ``"<op> <type> @
    <name stack>"``) and ``program_spans`` (count and host seconds of each
    ``serve.*`` span that starts in the window)."""
    r = T.reduce_events(device_ops, device_modules, spans)
    lo, hi = next((s, e) for n, s, e in spans if n == T.WINDOW_SPAN)
    idle: Dict[str, float] = defaultdict(float)
    for chip in device_ops:
        busy = T.union(T.clip([(s, e) for _, s, e in device_ops[chip]],
                              lo, hi))
        for k, v in split_gaps(busy, spans, lo, hi).items():
            idle[k] += v / len(device_ops)
    r["idle"] = dict(idle)
    prog: Dict[str, dict] = defaultdict(lambda: {"n": 0, "s": 0.0})
    for name, s, e in spans:
        if name.startswith("serve.") and lo <= s < hi:
            prog[name]["n"] += 1
            prog[name]["s"] += (e - s) * 1e-9
    r["program_spans"] = dict(prog)
    r["ticks"] = prog[TICK_SPAN]["n"] if TICK_SPAN in prog else 0
    r["scopes"], by_op = scope_seconds(device_ops, device_modules, op_names,
                                       lo, hi)
    r["ops_by_stack"] = {f"{op} @ {stack}": v
                         for (op, stack), v in by_op.items()}
    return r


def ms_per_tick(rec: dict, seconds: float) -> Optional[float]:
    """``seconds`` of the traced window in milliseconds per ``serve.tick``;
    None where the trace holds no tick span (a program without spans, or
    a trace not reduced here), never 0."""
    ticks = (rec.get("trace") or {}).get("ticks")
    return 1e3 * seconds / ticks if ticks else None


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_events(*read_xplane(paths[-1]))


# --- by hand: one traced cell ------------------------------------------------

def per_tick(tr: dict) -> dict:
    """The window in milliseconds per ``serve.tick``: device time per
    program and per scope, idle time per label, host time per program
    span."""
    n = tr["ticks"]
    mods = {k: 1e3 * v["s"] / n for k, v in tr["modules"].items()}
    idle = {k: 1e3 * v / n for k, v in tr["idle"].items()}
    return {"ticks": n, "window_ms": 1e3 * tr["window_s"] / n,
            "modules_ms": mods, "idle_ms": idle,
            "scopes_ms": {k: 1e3 * v / n for k, v in tr["scopes"].items()},
            "host_spans_ms": {k: 1e3 * v["s"] / n
                              for k, v in tr["program_spans"].items()}}


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to copy the window's .xplane.pb into")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import run as RUN
    from chipbench import peaks as P

    spec = RUN.load_spec(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        RUN.log("spans: needs a TPU")
        return 2
    # a cached executable keeps the metadata of the program that compiled
    # it unless metadata is part of the key: the scopes must be this tree's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    def reduce_and_keep(trace_dir):
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            for p in glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True):
                shutil.copy(p, args.keep)
        return reduce_dir(trace_dir)

    T.reduce_dir = reduce_and_keep      # measure() reduces through trace.py
    run_ = RUN.measure(spec, args.seed, args.seconds, True,
                       devs[:spec["workload"]["chips"]])
    e2e, extra = RUN.end_to_end(run_)
    drv, tr = run_["drv"], run_["trace"]
    w0, w1 = run_["w0"], run_["w1"]
    forwards = [f for t, f in drv.forwards if w0 < t <= w1]
    rec = RUN.layer_record(spec, run_, extra,
                           P.peaks_for(devs[0].device_kind))
    names = [m["name"] for m in spec["per_layer"]] + list(TICK_METRICS)
    metrics = {n: RUN.load_reader(n)(rec) for n in names}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": devs[0].device_kind, "end_to_end_traced": e2e,
        "metrics": metrics, "busy_s": tr["busy_s"],
        "window_s": tr["window_s"], "per_tick": per_tick(tr),
        "prefill_chunks": {
            "spans": tr["program_spans"].get("serve.chunk", {}).get("n", 0),
            "counter": run_["counters"]["prefill_chunks"],
            "harness_forwards": sum(
                1 for f in forwards if f["kind"] == "prefill")},
        "top_ops_by_stack": sorted(tr["ops_by_stack"].items(),
                                   key=lambda kv: -kv[1])[:30]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

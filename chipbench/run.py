#!/usr/bin/env python3
"""On-chip benchmark of the integer serving engine: one cell, one run.

    python3 chipbench/run.py --workload qwen3-4b.decode --seed 7 \
        --seconds 45 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``), a traffic mix
(``chipbench/traffic/<mix>.json``) and its engine settings
(``chipbench/cells/<cell>.json``); the configuration's ``"arch"`` names
its architecture module ``chipbench/arch/<arch>.py`` (the program's
configuration, set-up, plain reference and work counts: see
``arch/dense.py``); per-layer metrics are readers
``chipbench/metrics/<metric>.py``.  Everything is found by name.

One run, in one process that holds the cell's chips:

1. refuse without a TPU (exit 2, no result line);
2. turn on JAX's persistent compilation cache inside the checkout;
3. the architecture module makes the weights from the seed on the device,
   runs the program's calibration forward and integer fold, and frees
   the float weights;
4. build the engine from the cell's settings;
5. warm the decode graph and every prefill chunk length the mix can
   produce, through ``Engine.submit``/``poll``;
6. bring the traffic to steady state (closed loop: the first wave
   prefilled; open loop: arrivals running for the mix's warm-up);
7. measure for ``--seconds``: only ``submit``/``poll`` and the harness's
   own bookkeeping run; with ``--trace 1`` the window is traced;
8. free the engine, score a sample of the finished requests against the
   plain reference, and print the result as the last line of standard
   output (the numbers compared, with their limits, last on standard
   error and last in the result).

``setup_s`` is process start to window start.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import traffic as TR  # noqa: E402

DRAIN_CAP_S = 60.0      # after the window: wait this long for first tokens


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- the spec ---------------------------------------------------------------

def load_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, mix, engine settings and metric
    entries, all found by name from ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(x for x in bench["workloads"] if x["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[x['name'] for x in bench['workloads']]}") from None
    c = next(x for x in bench["configs"] if x["name"] == w["config"])
    config = json.loads((root / c["file"]).read_text())

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "workload": w,
        "config": config,
        "arch": load_arch(config, root / c["file"], root),
        "mix": TR.load_mix(w["traffic"], root / "chipbench"),
        "cell": json.loads(
            (root / "chipbench" / "cells" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def _load_module(kind: str, name: str, path: Path):
    mod_name = f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod     # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def load_arch(config: dict, path: Path, root: Path = ROOT):
    """The architecture module ``chipbench/arch/<arch>.py`` that the
    configuration read from ``path`` names."""
    if "arch" not in config:
        raise KeyError(f"{path}: the configuration names no \"arch\" "
                       f"(a module chipbench/arch/<arch>.py)")
    name = config["arch"]
    return _load_module("arch", name,
                        root / "chipbench" / "arch" / f"{name}.py")


def load_reader(name: str, root: Path = HERE):
    return _load_module("metric", name, root / "metrics" / f"{name}.py").read


# --- the run's bookkeeping ---------------------------------------------------

class Rec:
    """One request as the client sees it (harness clock)."""
    __slots__ = ("req", "prompt", "client", "due", "submit_t", "admit_poll",
                 "admit_t", "times", "toks", "final", "reason", "chunks")

    def __init__(self, req, client, due, submit_t):
        self.req, self.client, self.due, self.submit_t = \
            req, client, due, submit_t
        self.prompt = req.prompt
        self.admit_poll = self.admit_t = None
        self.times, self.toks = [], []
        self.final, self.reason, self.chunks = False, None, 0


class Driver:
    """Submits and polls the server, and records what each poll produced:
    tokens per request, admissions, and the forwards the tick ran
    (decode rows with their context lengths; prefill chunks, which the
    scheduler gives every prefilling slot once per tick from position 0
    in ``chunk``-token steps)."""

    def __init__(self, server, chunk: int, annotate):
        self.server, self.chunk, self.ann = server, chunk, annotate
        self.recs = {}
        self.waiting = set()
        self.prefilling = {}             # rid -> next chunk index
        self.polls = 0
        self.poll_log = []               # (t_end, tokens emitted)
        self.forwards = []               # (t_end, forward)
        self.completed = []
        self.chunk_mismatch = 0

    def submit(self, item, due: float, clock) -> Rec:
        from repro.serve.engine import Request
        with self.ann("chipbench.submit"):
            req = Request(prompt=item.prompt, max_new_tokens=item.max_new)
            rid = self.server.submit(req)
        rec = Rec(req, item.client, due, clock())
        self.recs[rid] = rec
        self.waiting.add(rid)
        return rec

    def poll(self, clock):
        from repro.serve.engine import RequestStatus
        with self.ann("chipbench.poll"):
            events = self.server.poll()
        t = clock()
        self.polls += 1
        with self.ann("chipbench.record"):
            for rid in [r for r in self.waiting
                        if self.recs[r].req.status is not RequestStatus.WAITING]:
                rec = self.recs[rid]
                rec.admit_poll, rec.admit_t = self.polls, t
                self.waiting.discard(rid)
                self.prefilling[rid] = 0
            # prefill chunks this tick: one per prefilling slot
            for rid, j in list(self.prefilling.items()):
                ln = len(self.recs[rid].prompt)
                pos0 = j * self.chunk
                ntok = min(self.chunk, ln - pos0)
                final = pos0 + ntok >= ln
                self.forwards.append((t, {"kind": "prefill", "pos0": pos0,
                                          "ntok": ntok, "final": final}))
                if final:
                    del self.prefilling[rid]
                    self.recs[rid].chunks = j + 1
                else:
                    self.prefilling[rid] = j + 1
            contexts, n_tok, done = [], 0, []
            for e in events:
                rec = self.recs[e.rid]
                if e.token is not None:
                    rec.toks.append(e.token)
                    rec.times.append(t)
                    n_tok += 1
                    if e.index >= 1:
                        contexts.append(len(rec.prompt) + e.index)
                    elif rec.chunks == 0:
                        self.chunk_mismatch += 1
                if e.final:
                    rec.final, rec.reason = True, e.finish_reason
                    done.append(rec)
            if contexts:
                self.forwards.append((t, {"kind": "decode",
                                          "contexts": contexts}))
            self.poll_log.append((t, n_tok))
            self.completed.extend(done)
        return done


def counters_of(server) -> dict:
    keys = ("decode_steps", "decode_tokens", "prefill_tokens",
            "prefill_chunks", "completed", "preemptions", "prefix_hits")
    return {k: server.counters[k] for k in keys}


# --- phases -----------------------------------------------------------------

def build_engine(spec, seed, devices):
    """The architecture's folded parameters -> engine.  Returns (server,
    program config)."""
    from repro.serve.engine import Engine, EngineConfig

    m, cell, arch = spec["config"]["model"], spec["cell"], spec["arch"]
    cfg = arch.program_config(m)
    folded = arch.folded(cfg, m, seed)
    gc.collect()
    e = cell["engine"]
    ecfg = EngineConfig(batch_slots=e["slots"], max_len=e["max_len"],
                        page_size=e["page"], n_pages=e["n_pages"],
                        max_prefill_chunk=e["chunk"], seed=seed)
    eng = Engine(cfg, folded, ecfg)
    if eng.layout != "paged" or eng.kv_bits != 8:
        raise RuntimeError(f"engine resolved to {eng.layout}/kv{eng.kv_bits}")
    del folded
    return eng, cfg


def warm(server, spec, vocab, clock, seed):
    """Compile (or load from the cache) every program the traffic uses: the
    decode step at the cell's slots and each prefill chunk length."""
    from repro.serve.engine import Request

    e = spec["cell"]["engine"]
    lo, hi = TR.prompt_range(spec["mix"])
    shapes = TR.chunk_shapes(lo, hi, e["chunk"], e["page"])
    rng = np.random.default_rng([seed, 5])
    t = clock()
    for n in shapes:
        server.submit(Request(
            prompt=rng.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=2))
    while server.has_work:
        server.poll()
    log(f"setup: warm {len(shapes)} chunk lengths + decode "
        f"{clock() - t:.2f} s")
    return shapes


def steady_closed(drv, tr, clock):
    """Submit the first wave and poll (the loop closed) until every first
    wave request has its first token."""
    first = [drv.submit(it, clock(), clock) for it in tr.first_wave()]
    while any(not r.toks for r in first):
        for rec in drv.poll(clock):
            drv.submit(tr.next_for(rec.client), clock(), clock)


def window_closed(drv, tr, clock, seconds):
    w0 = clock()
    end = w0 + seconds
    while True:
        for rec in drv.poll(clock):
            drv.submit(tr.next_for(rec.client), clock(), clock)
        if clock() >= end:
            return w0, clock()


def open_loop(drv, tr, clock, warm_s, seconds, trace_on, trace_off):
    """Arrivals from the generator's start; the window opens ``warm_s``
    later.  After it closes, arrivals go on until every request due in the
    window has its first token, or DRAIN_CAP_S passes."""
    g0 = clock()
    w0 = w1 = None
    due_in = []
    while True:
        now = clock()
        if w0 is None and now >= g0 + warm_s:
            trace_on()
            w0 = now = clock()
        for it in tr.due_until(now - g0):
            rec = drv.submit(it, g0 + it.due_s, clock)
            if w0 is not None and w1 is None and rec.due < w0 + seconds:
                due_in.append(rec)
        if not drv.server.has_work:
            time.sleep(max(0.0, min(0.002, g0 + tr.peek_due() - clock())))
            if w0 is not None and w1 is None and clock() >= w0 + seconds:
                w1 = clock()
                trace_off()
            continue
        drv.poll(clock)
        if w0 is not None and w1 is None and clock() >= w0 + seconds:
            w1 = clock()
            trace_off()
        if w1 is not None and (all(r.toks for r in due_in)
                               or clock() > w1 + DRAIN_CAP_S):
            return w0, w1, due_in


# --- the run ----------------------------------------------------------------

def measure(spec, seed: int, seconds: float, trace: bool, devices,
            fault=None) -> dict:
    """Set-up, window and bookkeeping of one run; returns the record the
    metrics are read from.  ``fault`` (tests only) is called with the
    engine before the window, to break the timed path underneath."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    clock = time.perf_counter
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {cache_dir}")
    compiles = {"n": 0, "s": 0.0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)

    m, cell, mix = spec["config"]["model"], spec["cell"], spec["mix"]
    eng, _ = build_engine(spec, seed, devices)
    server = eng
    warm(server, spec, m["vocab_size"], clock, seed)
    tr = TR.Traffic(mix, seed, m["vocab_size"])
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    state = {"span": None}

    def annotate(name):
        return jax.profiler.TraceAnnotation(name)

    def trace_on():
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
            state["span"] = jax.profiler.TraceAnnotation("chipbench.window")
            state["span"].__enter__()

    def trace_off():
        if trace and state["span"] is not None:
            state["span"].__exit__(None, None, None)
            state["span"] = None
            jax.profiler.stop_trace()

    drv = Driver(server, cell["engine"]["chunk"], annotate)
    if fault is not None:
        fault(eng)
    t = clock()
    c_setup = dict(compiles)
    due_in = []
    if tr.kind == "closed":
        steady_closed(drv, tr, clock)
        log(f"setup: steady state {clock() - t:.2f} s")
        c0 = counters_of(server)
        setup_s = clock() - T0
        n_comp0 = compiles["n"]
        trace_on()
        w0, w1 = window_closed(drv, tr, clock, seconds)
        trace_off()
    else:
        c0_holder = {}

        def on():
            c0_holder["c"] = counters_of(server)
            c0_holder["n"] = compiles["n"]
            c0_holder["setup"] = clock() - T0
            trace_on()

        def off():
            trace_off()
            c0_holder["c1"] = counters_of(server)
            c0_holder["n1"] = compiles["n"]

        w0, w1, due_in = open_loop(drv, tr, clock, mix["warm_s"], seconds,
                                   on, off)
        c0, n_comp0, setup_s = c0_holder["c"], c0_holder["n"], \
            c0_holder["setup"]
    c1 = counters_of(server) if tr.kind == "closed" else c0_holder["c1"]
    n_comp1 = compiles["n"] if tr.kind == "closed" else c0_holder["n1"]
    log(f"window: {w1 - w0:.3f} s, {drv.polls} polls in all, compiles in "
        f"window {n_comp1 - n_comp0}, compiles in set-up {c_setup['n']} "
        f"({c_setup['s']:.1f} s)")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    reduced = None
    if trace:
        from chipbench import trace as TRC
        t = clock()
        reduced = TRC.reduce_dir(tdir)
        log(f"trace: reduced in {clock() - t:.2f} s; busy "
            f"{reduced['busy_s']:.3f} s of {reduced['window_s']:.3f} s")
        if os.environ.get("CHIPBENCH_TRACE_DUMP"):
            out = Path(os.environ["CHIPBENCH_TRACE_DUMP"])
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(TRC.describe(tdir) + "\n" +
                           json.dumps(reduced, indent=1))
        shutil.rmtree(tdir, ignore_errors=True)
    return {"drv": drv, "w0": w0, "w1": w1, "due_in": due_in,
            "counters": {k: c1[k] - c0[k] for k in c0}, "setup_s": setup_s,
            "memory_peak_bytes": mem, "trace": reduced, "server": server,
            "eng": eng}


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def end_to_end(run: dict):
    """The client-side numbers of the window."""
    drv, w0, w1 = run["drv"], run["w0"], run["w1"]
    toks = sum(n for t, n in drv.poll_log if w0 < t <= w1)
    itl = []
    for rec in drv.recs.values():
        ts = rec.times
        itl.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]) if w0 < b <= w1)
    ttft = [1e3 * (r.times[0] - r.due) for r in run["due_in"] if r.times]
    qwait = [1e3 * (r.admit_t - r.due) for r in run["due_in"]
             if r.admit_t is not None]
    out = {"tokens_per_s": toks / (w1 - w0),
           "itl_p95_ms": float(np.percentile(itl, 95)) if itl else None,
           "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
           "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
           "setup_s": run["setup_s"]}
    late = [1e3 * (r.submit_t - r.due) for r in run["due_in"]]
    return out, {"ttft_ms": ttft, "queue_wait_ms": qwait, "itl_n": len(itl),
                 "tokens": toks, "late_ms": late}


def check_outputs(spec, seed, run, sample_n, control=False) -> dict:
    """Free the engine, then score a sample of finished requests against the
    architecture's reference.  Returns the numbers compared.  ``control``
    also reads the control's gaps on the same sample (``control.py``; the
    benchmark's own runs do not)."""
    from chipbench import check as C

    done = [{"prompt": r.prompt, "out": np.asarray(r.toks)}
            for r in run["drv"].completed
            if r.reason == "length" and r.toks]
    run["server"] = run["eng"] = run["drv"].server = None
    gc.collect()
    sample = C.sample_requests(done, sample_n, seed)
    return spec["arch"].score(spec["config"]["model"], seed, sample,
                              spec["cell"]["engine"]["max_len"], control)


def layer_record(spec, run_: dict, extra: dict, peaks) -> dict:
    """What the per-layer readers read: the run's counters and trace, and
    the work the window's forwards needed (the architecture's count)."""
    w0, w1 = run_["w0"], run_["w1"]
    forwards = [f for t, f in run_["drv"].forwards if w0 < t <= w1]
    m = spec["config"]["model"]
    return {"cell": spec["cell"], "model": m,
            "slots": spec["cell"]["engine"]["slots"],
            "counters": run_["counters"], "trace": run_["trace"],
            "peaks": peaks, "window_s": w1 - w0,
            "work": spec["arch"].window_work(m, forwards, peaks),
            "queue_wait_ms": extra["queue_wait_ms"],
            "ttft_ms": extra["ttft_ms"]}


def run(spec: dict, seed: int, seconds: float, trace: bool, devices,
        fault=None) -> dict:
    from chipbench import peaks as P

    cell = spec["cell"]
    run_ = measure(spec, seed, seconds, trace, devices, fault)
    e2e, extra = end_to_end(run_)
    drv = run_["drv"]
    w0, w1 = run_["w0"], run_["w1"]
    if spec["mix"]["kind"] != "closed":
        attempted = len(run_["due_in"])
    else:
        attempted = sum(1 for r in drv.recs.values()
                        if w0 <= r.submit_t < w1
                        or any(w0 < t <= w1 for t in r.times))
    failed = sum(1 for r in run_["due_in"] if not r.times) + sum(
        1 for r in drv.completed if r.reason not in ("length", "eos"))
    late = extra["late_ms"]
    log(f"generator: {len(late)} requests due in the window, lateness "
        f"p50 {np.median(late) if late else 0:.1f} ms, max "
        f"{max(late) if late else 0:.1f} ms")
    log(f"window: {extra['tokens']} tokens, {extra['itl_n']} token gaps, "
        f"{len(extra['ttft_ms'])} first tokens timed; counters "
        f"{run_['counters']}; chunk bookkeeping mismatches "
        f"{drv.chunk_mismatch}")
    kind = devices[0].device_kind
    peaks = P.peaks_for(kind) if devices[0].platform == "tpu" else P.V5E
    rec = layer_record(spec, run_, extra, peaks)
    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(run_["memory_peak_bytes"])}
    breakdown = None
    if trace and run_["trace"]:
        tr = run_["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        ops = sorted(tr["ops_shaped"].items(),
                     key=lambda kv: -kv[1]["s"])[:10]
        idle = sorted(tr["idle"].items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[k, v["s"]] for k, v in ops],
                     "idle_gaps": [[k, v] for k, v in idle]}
        log(f"trace modules: {tr['modules']}")
    t = time.perf_counter()
    got = check_outputs(spec, seed, run_, cell["check"]["sample"])
    g = got["program"]
    log(f"check: reference over {g['requests']} requests, {g['tokens']} "
        f"served tokens, {time.perf_counter() - t:.2f} s; widest gap "
        f"{g['widest']}, not the reference's first choice {g['not_first']}")
    lim = cell["check"]
    compared = {
        "logit_gap_mean": {"value": g["mean"],
                           "limit": lim["logit_gap_mean_max"]},
        "served_tokens_scored": {"value": g["tokens"],
                                 "limit": lim["min_tokens_scored"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (g["mean"] <= lim["logit_gap_mean_max"]
               and g["tokens"] >= lim["min_tokens_scored"]
               and failed == 0)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    chips = spec["workload"]["chips"]

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"chipbench: cell {args.workload} needs {chips} TPU chip(s); "
            f"JAX found {len(devs)} {devs[0].platform} device(s)")
        return 2
    result = run(spec, args.seed, args.seconds, bool(args.trace),
                 devs[:chips])
    for k, v in result["compared"].items():
        log(f"compared {k}: {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

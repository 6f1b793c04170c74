#!/usr/bin/env python3
"""On-chip smoke test of the integer serving engine (TPU only).

Drives the serving main path once, at the published width of qwen3-4b
(36 layers, d_model 2560, 32/8 heads, d_ff 9728, vocab 151,936) with
seeded random weights, in ONE process that holds the chip:

1. the compiled Pallas kernels against their ``kernels/ref.py`` oracles,
   bit for bit, at this model's shapes (int4 matmul, integer LayerNorm,
   paged decode and paged prefill attention);
2. seeded params -> calibration forward -> integer fold
   (``launch.serve.calibrated_folded``), float params freed before the
   engine is built;
3. ``make_engine`` with the default paged int8 layout; a few requests
   with a shared prefix (chunked prefill + prefix hits) drained through
   ``poll()``; every request must finish with its token count, every token
   in the vocabulary, and no kernel may run through the interpreter.

    python3 chip_smoke.py [--seed 0]
    python3 chip_smoke.py --chips 4      # the multi-chip paths only

``--chips 4`` runs only what needs several chips: a tp=4 engine against a
tp=1 engine on the same requests (greedy tokens must match), and four
one-chip replicas behind ``ReplicaRouter``, each on its own device, whose
outputs must equal a single engine's.

Exits non-zero, with no result line, when JAX finds no TPU.  The last line
of standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Wall times printed on earlier lines are one run's host clock (compiles
included), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3-4b"
PAGE = 16
PREFIX_LEN = 256          # shared system prompt: 16 pages
SUFFIX_LEN = 64
MAX_NEW = 16
CHUNK = 128               # max_prefill_chunk: prompts prefill in 3 chunks
SLOTS = 4
MAX_LEN = 512


def log(key, value):
    print(f"{key}: {value}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache hits cost
    close to nothing, so a warm cache shows up as a small number)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


# --- phase 1: compiled kernels vs oracles ---------------------------------

def check_kernels(cfg, rng):
    """Every main-path kernel, on the kernel backend in use (compiled on
    the chip), against its jnp oracle at the model's shapes; both run on
    the device and their integer outputs must be identical."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fixedpoint as fxp
    from repro.core import qsoftmax as qs
    from repro.core.qlayernorm import QLNParams
    from repro.core.qlinear import FoldedLinear
    from repro.kernels import ops
    from repro.kernels import ref as R

    impl = ops.backend()

    def same(name, got, want):
        got, want = np.asarray(got, np.int32), np.asarray(want, np.int32)
        if got.shape != want.shape or not np.array_equal(got, want):
            diff = (np.abs(got - want).max() if got.shape == want.shape
                    else f"shape {got.shape} vs {want.shape}")
            raise AssertionError(f"{name}: kernel != oracle (max |diff| "
                                 f"{diff}, {np.sum(got != want)} elements)")
        log(f"kernel {name}", f"bit-exact vs oracle {got.shape}")

    d, dff, hd = cfg.d_model, cfg.d_ff, cfg.hd
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    x = jnp.asarray(rng.integers(-127, 128, (SLOTS, d)), jnp.int8)
    for name, k, n in (("int4_matmul wq", d, nh * hd),
                       ("int4_matmul gate_up", d, dff),
                       ("int4_matmul down", dff, d)):
        xk = x if k == d else jnp.asarray(
            rng.integers(-127, 128, (SLOTS, k)), jnp.int8)
        M, sh = fxp.quantize_multiplier(2.0 / (k * 127))
        f = FoldedLinear(
            jnp.asarray(rng.integers(0, 256, (k // 2, n)), jnp.uint8),
            jnp.asarray(rng.integers(-2**15, 2**15, (n,)), jnp.int32),
            jnp.int32(M), jnp.int32(sh), 4)
        same(name, ops.linear_w4a8(xk, f, impl=impl),
             ops.linear_w4a8(xk, f, impl="ref"))

    M, sh = fxp.quantize_multiplier(1.0 / 2**10)
    p = QLNParams(gamma_i=jnp.asarray(rng.integers(-127, 128, (d,)), jnp.int8),
                  beta_aligned=jnp.zeros((d,), jnp.int32), M_out=jnp.int32(M),
                  shift_out=jnp.int32(sh),
                  subtract_mean=cfg.norm_type == "layernorm")
    same("quant_layernorm", ops.layernorm_q(x, p, impl=impl),
         ops.layernorm_q(x, p, impl="ref"))

    s_logit = 1.0 / (0.05 * np.sqrt(hd))
    mi, si = fxp.quantize_multiplier(1.0 / (s_logit * qs.LUT_DELTA))
    tail = (jnp.int32(mi), jnp.int32(si), jnp.asarray(R.make_exp_lut_q7()),
            jnp.float32(1.0 / s_logit), jnp.float32(1.0))
    nb = MAX_LEN // PAGE
    n_pages = SLOTS * nb + 1
    pool = lambda: jnp.asarray(                       # noqa: E731
        rng.integers(-64, 65, (n_pages, nkv, PAGE, hd)), jnp.int8)
    kp, vp = pool(), pool()
    btab = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(SLOTS, nb),
                       jnp.int32)
    q = jnp.asarray(rng.integers(-64, 65, (SLOTS, nkv, nh // nkv, hd)),
                    jnp.int8)
    lens = jnp.asarray([1, 17, 260, MAX_LEN], jnp.int32)
    same("paged_decode_attention",
         ops.paged_decode_attention_q(q, kp, vp, btab, lens, *tail,
                                      impl=impl),
         ops.paged_decode_attention_q(q, kp, vp, btab, lens, *tail,
                                      impl="ref"))
    qc = jnp.asarray(rng.integers(-64, 65, (1, nh, 2 * CHUNK, hd)), jnp.int8)
    pos0 = jnp.asarray([2 * CHUNK], jnp.int32)
    same("paged_prefill_attention",
         ops.paged_prefill_attention_q(qc, kp, vp, btab[:1], pos0, *tail,
                                       impl=impl),
         ops.paged_prefill_attention_q(qc, kp, vp, btab[:1], pos0, *tail,
                                       impl="ref"))


# --- phase 2: the model ---------------------------------------------------

def build_model(seed: int):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import calibrated_folded

    cfg = get_config(ARCH)
    rng = np.random.default_rng(seed)
    calib = jax.numpy.asarray(rng.integers(0, cfg.vocab_size, (2, 64)),
                              jax.numpy.int32)
    t0 = time.perf_counter()
    folded = calibrated_folded(cfg, jax.random.PRNGKey(seed), calib)
    jax.block_until_ready(folded)
    log("init+calibrate+fold wall time (s)", f"{time.perf_counter() - t0:.1f}")
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(folded))
    log("folded params bytes", n_bytes)
    return cfg, folded


def make_requests(cfg, seed: int, n: int = 4):
    """``n`` prompts of 320 tokens: all share a 256-token prefix, and the
    last repeats the first prompt exactly (its tokens must match)."""
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed + 1)
    prefix = rng.integers(0, cfg.vocab_size, PREFIX_LEN)
    suffixes = [rng.integers(0, cfg.vocab_size, SUFFIX_LEN)
                for _ in range(n - 1)]
    prompts = [np.concatenate([prefix, s]) for s in suffixes]
    prompts.append(prompts[0].copy())
    return [Request(prompt=p.astype(np.int32), max_new_tokens=MAX_NEW)
            for p in prompts]


def drain(server, requests):
    """Submit the first request, poll until its prefill has handed off (its
    pages are then registered), submit the rest, and poll until every
    request is final.  Returns each request's tokens, in order."""
    rids = [server.submit(requests[0])]
    events = {rids[0]: []}
    done = set()

    def tick():
        for e in server.poll():
            if e.token is not None:
                events[e.rid].append(e.token)
            if e.final:
                if e.finish_reason != "length":
                    raise AssertionError(
                        f"request {e.rid} ended with {e.finish_reason!r}")
                done.add(e.rid)

    while not events[rids[0]]:
        tick()
    for r in requests[1:]:
        rid = server.submit(r)
        rids.append(rid)
        events[rid] = []
    while len(done) < len(rids):
        tick()
    return [events[r] for r in rids]


def check_outputs(cfg, outs):
    for i, toks in enumerate(outs):
        if len(toks) != MAX_NEW:
            raise AssertionError(f"request {i}: {len(toks)} tokens, "
                                 f"expected {MAX_NEW}")
        bad = [t for t in toks if not 0 <= t < cfg.vocab_size]
        if bad:
            raise AssertionError(f"request {i}: tokens outside the "
                                 f"vocabulary: {bad}")
    if outs[-1] != outs[0]:
        raise AssertionError("the repeated prompt decoded differently: "
                             f"{outs[0]} vs {outs[-1]}")


def check_compiled_kernels(eng):
    """Trace the engine's hot graphs: each must contain Pallas kernels, and
    none may run through the interpreter."""
    import jax

    from repro.analysis.jaxpr_audit import pallas_calls

    for name, (fn, args) in eng.hot_graphs().items():
        calls = pallas_calls(jax.make_jaxpr(fn)(*args))
        interp = [c for c in calls if c.params.get("interpret")]
        if not calls or interp:
            raise AssertionError(f"graph {name}: {len(calls)} Pallas "
                                 f"kernels, {len(interp)} interpreted")
        log(f"graph {name}", f"{len(calls)} compiled Pallas kernels, "
            "none interpreted")


def serve_one_chip(args, clock, dev):
    import numpy as np

    from repro.serve.engine import EngineConfig, make_engine

    cfg, folded = build_model(args.seed)
    log("peak HBM after fold", peak_hbm(dev))
    check_kernels(cfg, np.random.default_rng(args.seed))
    eng = make_engine(cfg, folded, EngineConfig(
        batch_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
        max_prefill_chunk=CHUNK, seed=args.seed))
    del folded
    if eng.layout != "paged" or eng.kv_bits != 8:
        raise AssertionError(f"engine resolved to {eng.layout}/"
                             f"kv{eng.kv_bits}, expected paged int8")
    check_compiled_kernels(eng)
    reqs = make_requests(cfg, args.seed)
    c0, t0 = clock.total, time.perf_counter()
    outs = drain(eng, reqs)
    wall = time.perf_counter() - t0
    check_outputs(cfg, outs)
    c = eng.counters
    if c["chunked_prefills"] < 1 or c["prefix_hits"] < 1:
        raise AssertionError(f"expected chunked prefills and prefix hits, "
                             f"got {c['chunked_prefills']} / "
                             f"{c['prefix_hits']}")
    n_tok = sum(len(o) for o in outs)
    log("requests", f"{len(outs)} x {MAX_NEW} new tokens, prompts "
        f"{[len(r.prompt) for r in reqs]}")
    log("counters", {k: c[k] for k in ("prefill_tokens", "prefill_chunks",
                                       "chunked_prefills", "prefix_hits",
                                       "shared_rows", "decode_steps")})
    log("serving wall time incl. compile (s)", f"{wall:.2f}")
    log("compile seconds while serving", f"{clock.total - c0:.1f}")
    log("generated tokens", n_tok)
    log("first request tokens", outs[0])


# --- --chips 4: tensor parallelism and replicas ---------------------------

def serve_four_chips(args, devs):
    import jax

    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.router import ReplicaRouter

    cfg, folded = build_model(args.seed)
    # eight requests, so that each of the four replicas serves some
    reqs = lambda: make_requests(cfg, args.seed, n=8)  # noqa: E731
    base_cfg = dict(batch_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
                    max_prefill_chunk=CHUNK, seed=args.seed)

    def timed(name, server):
        t0 = time.perf_counter()
        outs = drain(server, reqs())
        log(f"{name} wall time incl. compile (s)",
            f"{time.perf_counter() - t0:.2f}")
        check_outputs(cfg, outs)
        return outs

    base = timed("tp=1", Engine(cfg, folded, EngineConfig(**base_cfg)))
    tp4 = Engine(cfg, folded, EngineConfig(**base_cfg, tp=4))
    pool_devs = {d for x in jax.tree.leaves(tp4.cache) for d in x.devices()}
    if len(pool_devs) != 4:
        raise AssertionError(f"tp=4 pool spans {len(pool_devs)} devices")
    if timed("tp=4", tp4) != base:
        raise AssertionError("tp=4 greedy tokens differ from tp=1")
    log("tp=4 vs tp=1", "greedy tokens identical")
    del tp4

    replicas = [Engine(cfg, folded, EngineConfig(**base_cfg, device=d))
                for d in devs[:4]]
    routed = timed("router over 4 replicas", ReplicaRouter(replicas))
    if routed != base:
        raise AssertionError("router outputs differ from a single engine")
    served = [r.counters["completed"] for r in replicas]
    if min(served) < 1:
        raise AssertionError(f"a replica served nothing: {served}")
    for i, (eng, d) in enumerate(zip(replicas, devs)):
        held = {x for leaf in jax.tree.leaves((eng.folded, eng.cache))
                for x in leaf.devices()}
        if held != {d}:
            raise AssertionError(f"replica {i} params/pool on {held}, "
                                 f"expected {d}")
    log("router", f"outputs identical to a single engine; completed per "
        f"replica {served} on devices {[d.id for d in devs[:4]]}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform} "
              f"({os.environ.get('JAX_PLATFORMS', 'JAX_PLATFORMS unset')})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    from repro.kernels import ops
    from repro.launch.compile_cache import configure_compile_cache

    if ops.backend() != "pallas":
        print(f"chip_smoke: kernel backend resolved to {ops.backend()!r} "
              "(REPRO_KERNELS must be unset or 'pallas')", file=sys.stderr)
        return 2
    log("device", f"{devs[0].device_kind} x{len(devs)}")
    log("kernel backend", ops.backend())
    log("compile cache", configure_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 1:
        serve_one_chip(args, clock, devs[0])
    else:
        serve_four_chips(args, devs)
    log("compile seconds (all)", f"{clock.total:.1f}")
    log("peak HBM device 0", peak_hbm(devs[0]))
    log("total wall time (s)", f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

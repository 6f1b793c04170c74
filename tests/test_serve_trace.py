"""The engine's trace spans and the integer forward's named scopes, read back
from a real profiler trace of a CPU smoke engine (paged, chunked prefill)."""
import glob
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import spans as CS  # noqa: E402
from repro.configs import smoke_config
from repro.models import fold as F
from repro.models import transformer as T
from repro.serve import stats
from repro.serve.engine import Engine, EngineConfig, Request

OP_NAME = re.compile(r'op_name="([^"]*)"')
COUNTED = ("ticks", "prefill_chunks", "decode_steps", "oneshot_prefills",
           "chunked_prefills")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced serving session: 3 prompts (one of 3 chunks) through 2
    slots, prefill chunks of 8 rows under a 16-token budget."""
    cfg = smoke_config("yi-6b")
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    _, obs, _ = T.forward(cfg, params, T.init_amax(cfg),
                          jax.random.randint(key, (2, 16), 0, cfg.vocab_size))
    eng = Engine(cfg, F.fold_params(cfg, params, obs), EngineConfig(
        batch_slots=2, max_len=64, cache_layout="paged", page_size=4,
        max_prefill_chunk=8, max_batched_tokens=16))
    rng = np.random.default_rng(0)

    def submit(lens):
        for n in lens:
            eng.submit(Request(prompt=rng.integers(0, cfg.vocab_size, n)
                               .astype(np.int32), max_new_tokens=5))

    submit([5])                         # compile outside the trace
    while eng.has_work:
        eng.poll()
    plans = []
    next_chunk = eng.sched.next_chunk

    def recording(*a, **kw):
        plan = next_chunk(*a, **kw)
        if plan is not None:
            b, st, pos0, ntok = plan
            plans.append((st.rid, b, pos0, ntok))
        return plan

    eng.sched.next_chunk = recording
    c0 = {k: eng.counters[k] for k in COUNTED}
    tdir = tmp_path_factory.mktemp("trace")
    submit([20, 6, 8])
    with jax.profiler.trace(str(tdir)):
        while eng.has_work:
            eng.poll()
    delta = {k: eng.counters[k] - c0[k] for k in COUNTED}
    path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
    return {"eng": eng, "spans": CS.span_events(path, ("serve.",)),
            "delta": delta, "plans": plans}


def test_every_span_name_is_in_the_schema(traced):
    names = {s[0] for s in traced["spans"]}
    assert names <= set(stats.SPANS)
    assert {"serve.tick", "serve.admit", "serve.chunk", "serve.grow",
            "serve.decode", "serve.fetch", "serve.pick"} <= names


def test_phase_spans_nest_inside_a_tick(traced):
    ticks = [(s, e) for n, s, e, _ in traced["spans"] if n == "serve.tick"]
    inner = [(n, s, e) for n, s, e, _ in traced["spans"]
             if n != "serve.tick"]
    assert inner
    for n, s, e in inner:
        assert any(ts <= s and e <= te for ts, te in ticks), n


def test_span_counts_match_the_counters(traced):
    spans, d = traced["spans"], traced["delta"]

    def count(name, **kw):
        return sum(1 for n, _, _, a in spans if n == name
                   and all(a.get(k) == v for k, v in kw.items()))

    assert count("serve.tick") == d["ticks"] > 0
    assert count("serve.chunk") == d["prefill_chunks"] == 5
    assert count("serve.decode") == d["decode_steps"] > 0
    assert count("serve.fetch", what="decode") == d["decode_steps"]
    assert count("serve.fetch", what="chunk") == \
        d["oneshot_prefills"] + d["chunked_prefills"] == 3
    assert count("serve.pick") == d["decode_steps"]
    ticks = sorted(a["tick"] for n, _, _, a in spans if n == "serve.tick")
    assert ticks == list(range(ticks[0], ticks[0] + d["ticks"]))


def test_chunk_kwargs_are_the_scheduler_plans(traced):
    chunks = sorted((s, a) for n, s, _, a in traced["spans"]
                    if n == "serve.chunk")
    got = [(a["rid"], a["slot"], a["pos0"], a["ntok"]) for _, a in chunks]
    assert got == traced["plans"]
    assert sorted(p[3] for p in got) == [4, 6, 8, 8, 8]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_compiled_programs_carry_the_scopes(traced, program):
    eng = traced["eng"]
    pos = np.zeros((eng.batch,), np.int32)
    btab = np.zeros_like(eng.block_tables)
    if program == "decode":
        lowered = eng._decode.lower(eng.folded, eng.cache,
                                    np.zeros((eng.batch, 1), np.int32), pos,
                                    btab)
    else:
        lowered = eng._prefill.lower(eng.folded, eng.cache,
                                     np.zeros((1, 8), np.int32), btab[:1],
                                     np.int32(0))
    text = lowered.compile().as_text()
    op_names = set(OP_NAME.findall(text))
    for scope in ("attn/qkv", "attn/core", "attn/kv_write", "mlp/up_gate",
                  "mlp/act_lut", "mlp/down", "norm", "lm_head"):
        assert any(CS.in_scope(o, scope) for o in op_names), scope

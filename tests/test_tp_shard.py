"""TP-sharded paged KV pool: sharded greedy outputs must be bit-identical
to the unsharded engine on every workload shape the bench gates — plain
mixed-length traffic, shared-prefix reuse, chunked long-prompt prefill, and
overload with forced preemption — and the host-side scheduler must remain a
single rank-agnostic authority (identical counters, identical per-tick
stats, allocator invariants clean every tick).

The TP=4 tests need 4 devices; on CPU run them under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the test-tp CI
lane sets this job-wide — the flag must be set before jax initializes, so
it cannot be toggled from inside an already-running suite; without it the
multi-device tests skip).  The tp=1 degenerate test drives the same
shard_map path on a single device and runs everywhere — the no-simulation
fallback that keeps the TP code exercised in the plain CPU lane.
"""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.mesh import make_tp_mesh
from repro.models import fold as F
from repro.models import transformer as T
from repro.serve.engine import (Engine, EngineConfig,
                                EngineConfigError, Request)

KEY = jax.random.PRNGKey(0)
NDEV = len(jax.devices())
multi = pytest.mark.skipif(
    NDEV < 4, reason="needs 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4 on CPU)")


@pytest.fixture(scope="module")
def folded_cfg():
    cfg = smoke_config("yi-6b")          # nh=4, nkv=4: TP=4 -> 1 head/rank
    params = T.init_params(cfg, KEY)
    amax = T.init_amax(cfg)
    calib = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    _, obs, _ = T.forward(cfg, params, amax, calib)
    return cfg, F.fold_params(cfg, params, obs)


def _requests(cfg, lens, max_news, seed=0, prefix_len=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    reqs = []
    for ln, mn in zip(lens, max_news):
        suffix = rng.integers(0, cfg.vocab_size, (ln,)).astype(np.int32)
        reqs.append(Request(prompt=np.concatenate([prefix, suffix]),
                            max_new_tokens=mn))
    return reqs


def _drive(eng, requests, max_ticks=3000):
    """Submit everything, step to completion, asserting the stats
    invariants + allocator sweep every tick (the per-tick sweep is what
    catches a rank-divergent scheduling decision the moment it happens,
    not after outputs already differ)."""
    for r in requests:
        eng.submit(r)
    ticks = 0
    while eng.sched.has_work:
        assert ticks < max_ticks, "engine livelocked"
        ticks += 1
        eng.step()
        g = eng.stats(check=True)
        assert g["decode_slots_active"] + g["prefill_slots"] \
            + g["free_slots"] == eng.batch
        assert g["pages_in_use"] + g["pages_free"] + g["pages_cached_lru"] \
            == g["pages_capacity"]
    return [r.out.tolist() for r in requests]


def _ab(cfg, folded, mkreqs, *, tp_kw, max_ticks=3000, **kw):
    """Run unsharded vs sharded on the same workload; outputs AND counters
    must match exactly (counters equality is the rank-agnostic-scheduling
    invariant: the sharded engine made the identical decision sequence)."""
    ref = Engine(cfg, folded, EngineConfig(**kw))
    out_ref = _drive(ref, mkreqs(), max_ticks=max_ticks)
    tp = Engine(cfg, folded, EngineConfig(**kw, **tp_kw))
    out_tp = _drive(tp, mkreqs(), max_ticks=max_ticks)
    assert out_tp == out_ref
    assert tp.counters == ref.counters
    return out_ref, ref, tp


@multi
def test_tp4_plain_token_identity(folded_cfg):
    cfg, folded = folded_cfg
    mk = lambda: _requests(cfg, [5, 9, 3, 12], [6, 4, 8, 5])
    _, ref, tp = _ab(cfg, folded, mk, tp_kw=dict(tp=4), batch_slots=3,
                     max_len=64, cache_layout="paged", page_size=4)
    assert tp.stats()["tp"] == 4 and ref.stats()["tp"] == 1


@multi
def test_tp4_pool_is_actually_sharded(folded_cfg):
    """Each rank's shard holds Hkv/tp heads of EVERY page — the memory win
    the tentpole exists for, asserted on device buffers, not specs."""
    cfg, folded = folded_cfg
    eng = Engine(cfg, folded, EngineConfig(batch_slots=2, max_len=64,
                                           cache_layout="paged", page_size=4,
                                           tp=4))
    leaf = eng.cache["slot0"]["k"]       # (n_reps, n_pages, Hkv, P, hd)
    shards = leaf.addressable_shards
    assert len(shards) == 4
    for s in shards:
        assert s.data.shape == (cfg.n_reps, eng.n_pages, cfg.n_kv_heads // 4,
                                eng.page_size, cfg.hd)


@multi
def test_tp4_prefix_reuse_token_identity(folded_cfg):
    """Shared system prompt: the replicated block table maps the same
    cached pages on every rank, so prefix hits (and the suffix-only
    prefill) survive sharding bit-exactly."""
    cfg, folded = folded_cfg
    mk = lambda: _requests(cfg, [4, 4, 6], [6, 6, 4], prefix_len=9)
    _, _, tp = _ab(cfg, folded, mk, tp_kw=dict(tp=4), batch_slots=2,
                   max_len=64, cache_layout="paged", page_size=4)
    assert tp.counters["prefix_hits"] >= 1


@multi
def test_tp4_longprompt_chunked_token_identity(folded_cfg):
    """Chunks are the cross-rank work-division unit: every rank runs the
    same page-aligned chunk on its own heads.  The chunked sharded run
    must match both the chunked and the one-shot unsharded runs."""
    cfg, folded = folded_cfg
    mk = lambda: _requests(cfg, [24, 4, 4], [4, 8, 8])
    kw = dict(batch_slots=3, max_len=64, cache_layout="paged", page_size=4)
    out_chunked, _, tp = _ab(cfg, folded, mk, tp_kw=dict(tp=4),
                             max_batched_tokens=16, max_prefill_chunk=8,
                             **kw)
    assert tp.counters["chunked_prefills"] >= 1
    # chunking changes latency, not tokens — sharded chunked == one-shot
    out_oneshot = _drive(Engine(cfg, folded, EngineConfig(**kw)), mk())
    assert out_chunked == out_oneshot


@multi
def test_tp4_overload_preemption_token_identity(folded_cfg):
    """Pool sized to force grow-path preemption: spill/restore decisions
    are made once on the host and apply to every rank's slice — the
    sharded starved run must preempt exactly like the unsharded starved
    run and both must match the unlimited-pool truth."""
    cfg, folded = folded_cfg
    mk = lambda: _requests(cfg, [4, 4], [12, 12])
    kw = dict(batch_slots=2, max_len=64, cache_layout="paged", page_size=4)
    truth = Engine(cfg, folded, EngineConfig(**kw))   # ample default pool
    out_truth = _drive(truth, mk())
    assert truth.counters["preemptions"] == 0
    out_starved, _, tp = _ab(cfg, folded, mk, tp_kw=dict(tp=4), n_pages=6,
                             **kw)
    assert tp.counters["preemptions"] >= 1
    assert tp.counters["restores"] == tp.counters["preemptions"]
    assert out_starved == out_truth


def _cycle_requests(cfg, lens, max_news, seed=7, period=3):
    """Prompt-lookup-friendly prompts (tiled short cycles) so speculative
    runs really exercise multi-token verify forwards."""
    rng = np.random.default_rng(seed)
    reqs = []
    for ln, mn in zip(lens, max_news):
        pat = rng.integers(0, cfg.vocab_size, (period,)).astype(np.int32)
        reqs.append(Request(prompt=np.tile(pat, ln // period + 1)[:ln],
                            max_new_tokens=mn))
    return reqs


@multi
def test_tp4_speculative_token_identity(folded_cfg):
    """Speculative decoding under TP=4: the verify forward shards like
    prefill (rank-local heads, replicated verify_rows), draft/accept
    decisions are host-side and rank-agnostic — sharded spec must match
    unsharded spec counter-for-counter AND both must match plain decode."""
    cfg, folded = folded_cfg
    mk = lambda: _cycle_requests(cfg, [5, 9, 3, 12], [8, 6, 8, 6])
    kw = dict(batch_slots=3, max_len=64, cache_layout="paged", page_size=4)
    out_plain = _drive(Engine(cfg, folded, EngineConfig(**kw)), mk())
    out_spec, _, tp = _ab(cfg, folded, mk, tp_kw=dict(tp=4),
                          spec_k=3, **kw)
    assert out_spec == out_plain
    assert tp.counters["drafted"] > 0


def test_tp1_degenerate_speculative_identity(folded_cfg):
    """Single-device shard_map fallback for the spec verify forward: runs
    in the plain CPU lane, keeps the sharded verify graph tested."""
    cfg, folded = folded_cfg
    mk = lambda: _cycle_requests(cfg, [5, 9, 3], [8, 6, 8])
    kw = dict(batch_slots=2, max_len=64, cache_layout="paged", page_size=4)
    out_plain = _drive(Engine(cfg, folded, EngineConfig(**kw)), mk())
    out_spec, ref, tp = _ab(cfg, folded, mk,
                            tp_kw=dict(mesh=make_tp_mesh(1)),
                            spec_k=3, **kw)
    assert out_spec == out_plain
    assert tp.mesh is not None and ref.mesh is None
    assert tp.counters["drafted"] > 0


@multi
def test_tp_rejects_indivisible_heads(folded_cfg):
    cfg, folded = folded_cfg                 # nkv=4: TP=3 can't slice it
    with pytest.raises(EngineConfigError, match="n_kv_heads"):
        Engine(cfg, folded, EngineConfig(batch_slots=2, max_len=64,
                                         cache_layout="paged", page_size=4,
                                         tp=3))


def test_tp_requires_paged_layout(folded_cfg):
    cfg, folded = folded_cfg
    with pytest.raises(EngineConfigError, match="paged"):
        Engine(cfg, folded, EngineConfig(batch_slots=2, max_len=64,
                                         cache_layout="contiguous",
                                         mesh=make_tp_mesh(1)))


def test_tp1_degenerate_shard_map_identity(folded_cfg):
    """tp=1 on an explicit 1-device mesh drives the full shard_map path
    (slice at rank 0, size-1 all_gather) with no simulation flag — the
    fallback that keeps TP code tested in the single-device CI lane."""
    cfg, folded = folded_cfg
    mk = lambda: _requests(cfg, [5, 9, 3], [6, 4, 8], prefix_len=5)
    _, ref, tp = _ab(cfg, folded, mk, tp_kw=dict(mesh=make_tp_mesh(1)),
                     batch_slots=2, max_len=64, cache_layout="paged",
                     page_size=4, max_batched_tokens=16, max_prefill_chunk=8)
    assert tp.mesh is not None and tp.tp == 1
    assert ref.mesh is None              # the A/B really was sharded-vs-not


def test_engine_device_places_params_and_pool(folded_cfg):
    """A one-chip replica pinned to a device keeps its params and pool
    there across serving, and serves the same tokens as an unpinned
    engine (the last device: device 3 under the 4-device flag)."""
    cfg, folded = folded_cfg
    dev = jax.devices()[-1]
    kw = dict(batch_slots=2, max_len=64, cache_layout="paged", page_size=4)
    pinned = Engine(cfg, folded, EngineConfig(**kw, device=dev))
    plain = Engine(cfg, folded, EngineConfig(**kw))
    mk = lambda: _requests(cfg, [5, 9], [6, 4], prefix_len=4)
    got = [r.out.tolist() for r in pinned.generate(mk())]
    want = [r.out.tolist() for r in plain.generate(mk())]
    assert got == want
    held = {d for leaf in jax.tree.leaves((pinned.folded, pinned.cache))
            for d in leaf.devices()}
    assert held == {dev}


def test_engine_device_rejects_tp(folded_cfg):
    cfg, folded = folded_cfg
    with pytest.raises(EngineConfigError, match="device"):
        Engine(cfg, folded, EngineConfig(batch_slots=2, max_len=64,
                                         mesh=make_tp_mesh(1),
                                         device=jax.devices()[0]))

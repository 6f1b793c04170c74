"""Intentionally-broken fixtures that prove the analysis subsystem works.

A static checker that never fires is indistinguishable from one that
can't.  Each fixture here plants exactly one of the bugs the auditor and
pallas lint exist to catch — a seeded f32 matmul on the int path, an int8
dot that accumulates narrow, a whole-pool float cast outside a kernel
boundary, a clobbered donation, aliased pool leaves, an out-of-range /
unclamped index map — and ``run_self_test`` asserts the expected rule id
is raised (and that the two blessed negative controls stay clean).  The
CI analyze lane runs this before trusting a zero-violation report.
"""
from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_audit, pallas_lint
from repro.analysis.jaxpr_audit import (audit_cache_aliasing, audit_graph,
                                        pool_threshold_elems)


class Fixture(NamedTuple):
    expected_rule: str    # "" for a negative control (must stay clean)
    run: Callable[[], List[jaxpr_audit.Violation]]


def _cache():
    # miniature paged pool: two >=4-D int8 payload leaves, 1024 elems each
    # (pool threshold = 512, far above the fixtures' activations)
    return {"k": jnp.zeros((1, 4, 16, 2, 8), jnp.int8),
            "v": jnp.ones((1, 4, 16, 2, 8), jnp.int8)}


def _args():
    cache = _cache()
    params = {"w": jnp.ones((8, 8), jnp.int8)}
    x = jnp.ones((2, 8), jnp.float32)
    return params, cache, x


def _audit(fn, *, donate: bool = True) -> List[jaxpr_audit.Violation]:
    args = _args()
    jitted = jax.jit(fn, donate_argnums=(1,)) if donate else jax.jit(fn)
    res = audit_graph(jitted, args, graph=f"fixture:{fn.__name__}",
                      pool_threshold=pool_threshold_elems(args[1]))
    return res.violations


# --- jaxpr-rule fixtures -------------------------------------------------

def _bad_fdot(params, cache, x):
    # launders the int8 weight into a float matmul: INT-DOT-FLOAT
    wf = params["w"].astype(jnp.float32) / 127.0
    return jnp.dot(x, wf), cache


def _bad_acc(params, cache, x):
    # int8 x int8 dot without preferred_element_type=int32: INT-DOT-ACC
    xq = jnp.clip(jnp.round(x * 16.0), -127, 127).astype(jnp.int8)
    y = jax.lax.dot_general(xq, params["w"], (((1,), (0,)), ((), ())))
    return y, cache


def _bad_pool_cast(_params, cache, x):
    # dequantizes the whole pool in open code: POOL-FLOAT-CAST
    kf = cache["k"].astype(jnp.float32)
    return x + kf.sum(), cache


def _clean(params, cache, x):
    # the shape of a correct hot graph: int dot with wide accumulate,
    # activation-scale casts only
    xq = jnp.clip(jnp.round(x * 16.0), -127, 127).astype(jnp.int8)
    y = jax.lax.dot_general(xq, params["w"], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) / 256.0, cache


@jax.jit
def _blessed_dequant(pool):
    return pool.astype(jnp.float32)


def _blessed_pool_cast(_params, cache, x):
    # same whole-pool cast as _bad_pool_cast, but inside a registered
    # kernel-boundary scope: must NOT be flagged
    kf = _blessed_dequant(cache["k"])
    return x + kf.sum(), cache


def _run_blessed() -> List[jaxpr_audit.Violation]:
    args = _args()
    jitted = jax.jit(_blessed_pool_cast, donate_argnums=(1,))
    res = audit_graph(jitted, args, graph="fixture:_blessed_pool_cast",
                      pool_threshold=pool_threshold_elems(args[1]),
                      boundaries={"_blessed_dequant": "fixture boundary"})
    return res.violations


def _run_aliased() -> List[jaxpr_audit.Violation]:
    # one jnp array reused for two pool leaves — the PR 7 double-donation
    shared = jnp.zeros((1, 4, 16, 2, 8), jnp.int8)
    return audit_cache_aliasing({"k": shared, "v": shared},
                                graph="fixture:aliased")


# --- pallas index-map fixtures ------------------------------------------

def _oob_decode_map(_bkv):
    def kv_map(bb, h, k, lens):    # noqa: ARG001 - index-map signature
        return (bb, k + 1, h, 0)   # off-by-one: last block out of range
    return kv_map


def _dead_unclamped_decode_map(_bkv):
    def kv_map(bb, h, k, lens):    # noqa: ARG001 - index-map signature
        return (bb, k, h, 0)       # in range, but dead blocks re-DMA
    return kv_map


def _trash_paged_map(_psize, ppb, i):
    def kv_map(bb, j, lens, btab):       # noqa: ARG001
        k = min(j * ppb + i, btab.shape[1] - 1)
        return (btab[bb, k], 0, 0, 0)    # dead k reads the trash page
    return kv_map


def _overrun_paged_steps(psize, ppb):
    def step_pages(bb, step, lens, btab):
        live = (int(lens[bb]) + psize - 1) // psize
        count = max(0, min(live + 1 - step * ppb, ppb))  # one page too many
        return count, lambda i: btab[bb, min(step * ppb + i,
                                             btab.shape[1] - 1)]
    return step_pages


FIXTURES: Dict[str, Fixture] = {
    "seeded_f32_matmul": Fixture(
        "INT-DOT-FLOAT", lambda: _audit(_bad_fdot)),
    "narrow_accumulate": Fixture(
        "INT-DOT-ACC", lambda: _audit(_bad_acc)),
    "open_pool_dequant": Fixture(
        "POOL-FLOAT-CAST", lambda: _audit(_bad_pool_cast)),
    "clobbered_donation": Fixture(
        "DONATION", lambda: _audit(_clean, donate=False)),
    "aliased_pool_leaves": Fixture(
        "DONATION-ALIAS", _run_aliased),
    "idxmap_out_of_range": Fixture(
        "IDXMAP-RANGE",
        lambda: pallas_lint.check_decode_kv_map(
            _oob_decode_map, kernel="fixture:oob_decode")),
    "idxmap_dead_unclamped": Fixture(
        "IDXMAP-CLAMP",
        lambda: pallas_lint.check_decode_kv_map(
            _dead_unclamped_decode_map, kernel="fixture:dead_unclamped")),
    "idxmap_paged_trash": Fixture(
        "IDXMAP-RANGE",
        lambda: pallas_lint.check_paged_decode_kv_map(
            _trash_paged_map, kernel="fixture:paged_trash")),
    "idxmap_paged_overrun": Fixture(
        "IDXMAP-RANGE",
        lambda: pallas_lint.check_paged_decode_kv_map(
            step_factory=_overrun_paged_steps,
            kernel="fixture:paged_overrun")),
    # negative controls: a correct graph and a boundary-blessed pool cast
    "clean_int_graph": Fixture("", lambda: _audit(_clean)),
    "blessed_pool_cast": Fixture("", _run_blessed),
}


def run_self_test() -> Dict:
    """Run every fixture; each broken one must raise its expected rule id,
    each negative control must stay clean.  Returns a JSON-able summary
    with an overall ``ok`` flag."""
    results = {}
    for name, fx in FIXTURES.items():
        viols = fx.run()
        rules = sorted({v.rule for v in viols})
        ok = fx.expected_rule in rules if fx.expected_rule else not rules
        results[name] = {
            "expected_rule": fx.expected_rule,
            "flagged_rules": rules,
            "ok": ok,
            "violations": [v.to_dict() for v in viols],
        }
    return {"ok": all(r["ok"] for r in results.values()),
            "fixtures": results}

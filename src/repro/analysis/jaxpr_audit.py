"""Integer-datapath auditor: walk a hot graph's ClosedJaxpr and enforce
the serving engine's declared invariants as machine-checked rules.

The engine's contract (PAPER.md: *fully* quantized BERT; I-BERT's lesson:
integer pipelines silently regress to float one op at a time) is defended
at runtime by bit-identity tests — but those can't localize *which eqn*
broke the contract.  This module can.  Rules, each with a stable id:

``INT-DOT-FLOAT``
    No f32/bf16/f16 ``dot_general`` reachable from quantized operands on
    the serve path.  Taint starts at every narrow-int (int4/int8/uint8)
    invar/const and propagates through all eqns (incl. nested scopes), so
    a float matmul fed — however indirectly — by quantized data is flagged
    even if someone laundered the dtype through elementwise ops first.
    Float *elementwise* islands (RoPE, the fp32 softmax carry, the logits
    exit) are allowed; float MXU work is not.

``INT-DOT-ACC``
    Integer ``dot_general`` must accumulate at >= 32 bits (the kernels pass
    ``preferred_element_type=jnp.int32``).  An int8 dot that comes out int8
    is an overflow bug XLA will happily compile.

``LATTICE-MIXED``
    Dtype-promotion lattice check on every eqn: arithmetic primitives must
    see operands of one kind (all-integer or all-float).  jax's strict
    jaxpr typing makes this unreachable today — the rule exists so a
    future custom primitive or lowering change that smuggles mixed-kind
    arithmetic in gets caught, not absorbed.

``POOL-FLOAT-CAST``
    No pool-scale ``convert_element_type`` from a narrow-int dtype to
    float outside a registered kernel boundary
    (``repro.analysis.boundary``).  The threshold is half the smallest KV
    pool payload leaf — activations sit orders of magnitude below it, a
    dequantized pool (or gathered whole-chain view) above.

``DONATION``
    Every cache pool leaf must appear donated (``donated_invars``) on the
    hot graph's jit eqn — a dropped donation doubles pool HBM.

``DONATION-ALIAS``
    No two live cache leaves may share a device buffer: XLA refuses (or
    silently copies) double-donated aliased buffers — the class PR 7 hit
    when the kv4 scale leaves shared one ``jnp.full``.

``audit_graph`` runs the jaxpr-level rules on one ``(fn, args)`` hot
graph; ``audit_engine`` runs every hot graph of a live Engine plus the
aliasing check and returns per-graph results.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

import jax

from jax.extend import core as jcore

from repro.analysis import boundary as boundary_mod

NARROW_INT = ("int4", "uint4", "int8", "uint8")
FLOAT_KINDS = ("float16", "bfloat16", "float32", "float64")
WIDE_INT = ("int32", "uint32", "int64", "uint64")

# primitives audited by the LATTICE-MIXED rule (operand kinds must agree)
ARITH_PRIMS = frozenset({"add", "sub", "mul", "div", "rem", "pow", "max",
                         "min", "atan2", "nextafter"})


@dataclasses.dataclass
class Violation:
    rule: str
    graph: str
    scope: str       # nested eqn path, e.g. "/decode_step/scan"
    detail: str

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AuditResult:
    graph: str
    n_eqns: int = 0
    violations: List[Violation] = dataclasses.field(default_factory=list)
    # dtype -> primitive name -> eqn count (by first output's dtype)
    op_histogram: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    float_prims: Set[str] = dataclasses.field(default_factory=set)

    @property
    def float_eqns(self) -> int:
        return sum(n for dt, prims in self.op_histogram.items()
                   if dt in FLOAT_KINDS for n in prims.values())


def _dtype_name(aval) -> Optional[str]:
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _is_narrow_int(aval) -> bool:
    return _dtype_name(aval) in NARROW_INT


def _is_float(aval) -> bool:
    return _dtype_name(aval) in FLOAT_KINDS


def _kind(aval) -> Optional[str]:
    dt = _dtype_name(aval)
    if dt is None:
        return None
    if dt in FLOAT_KINDS:
        return "float"
    if dt in NARROW_INT + WIDE_INT:
        return "int"
    return None    # bool, etc. — not lattice-checked


def _sub_jaxprs(eqn) -> List[Tuple[str, jcore.Jaxpr, Optional[List[bool]]]]:
    """(scope_name, sub_jaxpr, invar_taint_map) for every sub-jaxpr of an
    eqn.  ``invar_taint_map`` is None when the mapping is 1:1 positional
    with ``eqn.invars`` (the recursion derives it); otherwise it is the
    explicit per-sub-invar taint seed (conservative where unknown)."""
    prim, params = eqn.primitive.name, eqn.params
    subs: List[Tuple[str, jcore.Jaxpr, Optional[List[bool]]]] = []
    if prim in ("jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
                "remat", "checkpoint", "shard_map"):
        j = (params.get("jaxpr") or params.get("call_jaxpr")
             or params.get("fun_jaxpr"))
        if j is not None:
            name = params.get("name") or prim
            subs.append((str(name), _as_open(j), None))
    elif prim == "scan":
        subs.append(("scan", _as_open(params["jaxpr"]), None))
    elif prim == "while":
        subs.append(("while_cond", _as_open(params["cond_jaxpr"]), "all"))
        subs.append(("while_body", _as_open(params["body_jaxpr"]), "all"))
    elif prim == "cond":
        for i, br in enumerate(params["branches"]):
            subs.append((f"cond_branch{i}", _as_open(br), "skip_pred"))
    else:
        # unknown higher-order primitive: recurse conservatively into any
        # jaxpr-valued param with every sub-invar tainted
        for v in params.values():
            if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                subs.append((prim, _as_open(v), "all"))
    return subs


def _as_open(j) -> jcore.Jaxpr:
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def pallas_calls(closed) -> List:
    """Every ``pallas_call`` eqn reachable from a traced graph (kernel
    bodies are not descended into).  Their ``interpret`` param says
    whether the kernel would run compiled or through the interpreter."""
    found: List = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
                continue
            for _, sub, _ in _sub_jaxprs(eqn):
                walk(sub)

    walk(_as_open(closed))
    return found


def audit_graph(fn, args, *, graph: str, pool_threshold: int,
                boundaries: Optional[Dict[str, str]] = None,
                check_donation: bool = True,
                donate_argnums: Tuple[int, ...] = (1,)) -> AuditResult:
    """Trace ``fn(*args)`` to a jaxpr and run every jaxpr-level rule.

    ``pool_threshold`` is the element count above which an int->float
    convert counts as pool-scale; ``donate_argnums`` names the positional
    args whose leaves must be donated (the cache), checked against the
    traced jit eqn's ``donated_invars``."""
    if boundaries is None:
        boundaries = dict(boundary_mod.REGISTRY)
    closed = jax.make_jaxpr(fn)(*args)
    res = AuditResult(graph=graph)

    taint: Dict[int, bool] = {}

    def seed(var, is_tainted):
        taint[id(var)] = bool(is_tainted)

    def tainted(atom) -> bool:
        if isinstance(atom, jcore.Literal):
            return _is_narrow_int(atom.aval)
        return taint.get(id(atom), _is_narrow_int(atom.aval))

    def walk(jaxpr: jcore.Jaxpr, scope: str, in_boundary: bool):
        for cv in jaxpr.constvars:
            seed(cv, _is_narrow_int(cv.aval))
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            res.n_eqns += 1
            in_taint = any(tainted(a) for a in eqn.invars)
            out_aval = eqn.outvars[0].aval if eqn.outvars else None
            dt = _dtype_name(out_aval) if out_aval is not None else None
            if dt is not None:
                hist = res.op_histogram.setdefault(dt, {})
                hist[prim] = hist.get(prim, 0) + 1
                if dt in FLOAT_KINDS:
                    res.float_prims.add(prim)

            if prim == "dot_general":
                operand_kinds = {_kind(a.aval) for a in eqn.invars}
                out_float = out_aval is not None and _is_float(out_aval)
                if (out_float or "float" in operand_kinds) and in_taint:
                    res.violations.append(Violation(
                        "INT-DOT-FLOAT", graph, scope,
                        f"dot_general with float dtype ({dt}) reachable "
                        f"from quantized operands"))
                if operand_kinds == {"int"} and dt not in WIDE_INT:
                    res.violations.append(Violation(
                        "INT-DOT-ACC", graph, scope,
                        f"integer dot_general accumulates in {dt}; "
                        "pass preferred_element_type=jnp.int32"))
            elif prim in ARITH_PRIMS:
                kinds = {_kind(a.aval) for a in eqn.invars
                         if getattr(a.aval, "shape", None) is not None}
                kinds.discard(None)
                if len(kinds) > 1:
                    res.violations.append(Violation(
                        "LATTICE-MIXED", graph, scope,
                        f"{prim} mixes operand kinds {sorted(kinds)}"))
            elif prim == "convert_element_type" and not in_boundary:
                src = eqn.invars[0].aval
                if (_is_narrow_int(src) and _is_float(out_aval)
                        and src.size >= pool_threshold):
                    res.violations.append(Violation(
                        "POOL-FLOAT-CAST", graph, scope,
                        f"pool-scale convert {_dtype_name(src)}->{dt} of "
                        f"{src.size} elems (threshold {pool_threshold}) "
                        "outside a registered kernel boundary"))

            for name, sub, taint_map in _sub_jaxprs(eqn):
                sub_boundary = in_boundary or name in boundaries
                if taint_map is None and len(sub.invars) == len(eqn.invars):
                    seeds = [tainted(a) for a in eqn.invars]
                elif taint_map == "skip_pred" \
                        and len(sub.invars) == len(eqn.invars) - 1:
                    seeds = [tainted(a) for a in eqn.invars[1:]]
                else:
                    seeds = [True] * len(sub.invars)
                for var, s in zip(sub.invars, seeds, strict=True):
                    seed(var, s)
                walk(sub, f"{scope}/{name}", sub_boundary)
                # taint of sub outvars flows to this eqn's outvars where
                # the arity matches (scan: carry+ys align; cond branches
                # OR together)
                if len(sub.outvars) == len(eqn.outvars):
                    for ov, sv in zip(eqn.outvars, sub.outvars, strict=True):
                        seed(ov, tainted(sv) or taint.get(id(ov), False))

            for ov in eqn.outvars:
                if id(ov) not in taint:
                    seed(ov, in_taint)

    for iv in closed.jaxpr.invars:
        seed(iv, _is_narrow_int(iv.aval))
    walk(closed.jaxpr, "", False)

    if check_donation:
        res.violations.extend(_audit_donation(
            closed, args, graph=graph, donate_argnums=donate_argnums))
    return res


def _audit_donation(closed, args, *, graph: str,
                    donate_argnums: Tuple[int, ...]) -> List[Violation]:
    """The traced fn is jitted, so the outer jaxpr is a single jit eqn
    whose ``donated_invars`` must cover every leaf of the donated args."""
    out: List[Violation] = []
    jit_eqns = [e for e in closed.jaxpr.eqns if e.primitive.name == "jit"]
    if not jit_eqns:
        return [Violation("DONATION", graph, "",
                          "no jit eqn found — hot graph is not jitted")]
    eqn = jit_eqns[0]
    donated = eqn.params.get("donated_invars")
    if donated is None:
        return [Violation("DONATION", graph, "",
                          "jit eqn carries no donated_invars")]
    # flat positions of each positional arg's leaves
    sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    offsets = [sum(sizes[:i]) for i in range(len(sizes))]
    if len(donated) != sum(sizes):
        return [Violation("DONATION", graph, "",
                          f"donated_invars length {len(donated)} != "
                          f"{sum(sizes)} flat args — cannot map leaves")]
    for argnum in donate_argnums:
        for j in range(sizes[argnum]):
            flat = offsets[argnum] + j
            if not donated[flat]:
                out.append(Violation(
                    "DONATION", graph, "",
                    f"cache leaf {j} (flat invar {flat}) of arg {argnum} "
                    "is not donated"))
    return out


def audit_cache_aliasing(cache, *, graph: str = "cache") -> List[Violation]:
    """No two pool leaves may share a device buffer (the double-donation
    class: XLA either refuses or silently copies aliased donated buffers).
    Checked on the LIVE pytree — jaxpr tracing cannot see value aliasing."""
    out: List[Violation] = []
    seen: Dict[Tuple, str] = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in leaves:
        if not isinstance(leaf, jax.Array):
            continue
        for shard in leaf.addressable_shards:
            key = (repr(shard.device), shard.data.unsafe_buffer_pointer())
            name = jax.tree_util.keystr(path)
            if key in seen:
                out.append(Violation(
                    "DONATION-ALIAS", graph, name,
                    f"leaf shares a device buffer with {seen[key]} — "
                    "double donation (one jnp array reused across leaves)"))
            else:
                seen[key] = name
    return out


def pool_threshold_elems(cache) -> int:
    """Half the smallest KV pool payload leaf's element count: activations
    sit far below, any whole-pool (or gathered whole-chain) dequant above.
    Payload leaves are the >=4-D pool arrays; 2-D kv4 scale leaves and
    non-paged layouts fall back to the largest leaf."""
    leaves = [l for l in jax.tree_util.tree_leaves(cache)
              if hasattr(l, "ndim")]
    pools = [l.size for l in leaves if l.ndim >= 4]
    if not pools:
        pools = [max((l.size for l in leaves), default=2)]
    return max(min(pools) // 2, 1)


def audit_engine(engine, *, graphs=None) -> Dict[str, AuditResult]:
    """Run every jaxpr-level rule over each hot graph of a live Engine,
    plus the live-buffer aliasing check (attached to the first graph)."""
    hot = engine.hot_graphs()
    if graphs is not None:
        hot = {k: v for k, v in hot.items() if k in graphs}
    thr = pool_threshold_elems(engine.cache)
    results: Dict[str, AuditResult] = {}
    for name, (fn, args) in hot.items():
        results[name] = audit_graph(fn, args, graph=name,
                                    pool_threshold=thr)
    if results:
        first = next(iter(results.values()))
        first.violations.extend(audit_cache_aliasing(engine.cache))
    return results


def lowered_hlo(fn, args) -> str:
    """Post-optimization HLO text of a hot graph (for bytes-by-dtype via
    ``repro.analysis.hlo_cost``)."""
    return fn.lower(*args).compile().as_text()


__all__ = [
    "AuditResult", "Violation", "audit_graph", "audit_cache_aliasing",
    "audit_engine", "pool_threshold_elems", "lowered_hlo",
    "NARROW_INT", "FLOAT_KINDS",
]

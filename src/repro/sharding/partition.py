"""Name-based parameter -> PartitionSpec rules (Megatron TP + FSDP over DP),
plus activation-sharding helpers.

Axes: ``data`` (+ ``pod`` composed in front on multi-pod meshes) carry the
batch and the FSDP shard of weights; ``model`` carries tensor parallelism:
column-parallel on QKV/gate/up (output dim), row-parallel on O/down
(contraction dim, XLA inserts the all-reduce), vocab-parallel embeddings and
LM head, expert-FFN-dim parallelism for MoE, state-dim parallelism for Mamba.

FSDP: the non-'model' weight dim is additionally sharded over the DP axes;
XLA all-gathers per layer (ZeRO-3 semantics).  Toggled per step-build —
serving never uses FSDP (weights are int4 and must be resident).
"""
from __future__ import annotations

import re
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --- rule tables --------------------------------------------------------------
# (regex on 'path/like/this', spec builder given (dp, fsdp) axis names)
# Paths are relative; leading 'blocks/slotN/' has a stacked (n_reps) dim 0
# which is always unsharded (scan axis).

def _qat_rules(_dp, fs):
    return [
        (r"embed/tokens$",      P("model", fs)),
        (r"embed/pos$",         P(None, None)),
        (r"embed/codebooks$",   P(None, "model", fs)),
        (r"attn/wq$",           P(None, fs, "model")),
        (r"attn/wk$",           P(None, fs, "model")),
        (r"attn/wv$",           P(None, fs, "model")),
        (r"attn/wo$",           P(None, "model", fs)),
        (r"attn/b[qkv]$",       P(None, "model")),
        (r"attn/bo$",           P(None, None)),
        (r"attn/[qk]n$",        P(None, None)),
        (r"mlp/wg$",            P(None, fs, "model")),
        (r"mlp/wu$",            P(None, fs, "model")),
        (r"mlp/wd$",            P(None, "model", fs)),
        (r"mlp/w1$",            P(None, fs, "model")),
        (r"mlp/w2$",            P(None, "model", fs)),
        (r"mlp/b1$",            P(None, "model")),
        (r"mlp/b2$",            P(None, None)),
        (r"moe/router$",        P(None, None, None)),
        (r"moe/(experts|shared)/wg$", P(None, None, fs, "model")),
        (r"moe/(experts|shared)/wu$", P(None, None, fs, "model")),
        (r"moe/(experts|shared)/wd$", P(None, None, "model", fs)),
        # mamba: d_in dims sharded over model (elementwise-parallel scan)
        (r"mixer/w_in$",        P(None, fs, "model")),
        (r"mixer/conv_w$",      P(None, None, "model")),
        (r"mixer/conv_b$",      P(None, "model")),
        (r"mixer/w_x$",         P(None, "model", None)),
        (r"mixer/w_dt$",        P(None, None, "model")),
        (r"mixer/dt_bias$",     P(None, "model")),
        (r"mixer/A_log$",       P(None, "model", None)),
        (r"mixer/D$",           P(None, "model")),
        (r"mixer/w_out$",       P(None, "model", fs)),
        # xlstm: project onto model over the wide dim
        (r"mixer/w[qkv]$",      P(None, fs, "model")),
        (r"mixer/wo$",          P(None, "model", fs)),
        (r"mixer/w_[io]g$",     P(None, None, None)),
        (r"mixer/w_fg$",        P(None, None, None)),
        (r"mixer/w_[zifo]$",    P(None, fs, "model")),
        (r"mixer/b_[zifo]g?$",  P(None, "model")),
        (r"mixer/r$",           P(None, None, None, None)),
        (r"mixer/ln_y$",        P(None, None)),
        (r"lm_head$",           P(fs, "model")),           # (d, V) or (K,d,V)
        (r"(norm1|norm2|final_norm)/(gamma|beta)$", P(None)),
        (r"pooler/w$",          P(None, None)),
        (r"classifier/w$",      P(None, None)),
    ]


def _serve_rules(_dp):
    """Folded-int serving: no FSDP; packed dim0 = K//2 follows K's spec."""
    return [
        (r"embed/tokens_i8$",    P("model", None)),
        (r"embed/pos_i8$",       P(None, None)),
        (r"embed/codebooks_i8$", P(None, "model", None)),
        (r"w[qkv]/(w|b)$",       P(None, None, "model")),
        (r"wo/w$",               P(None, "model", None)),
        (r"wo/b$",               P(None, None)),
        (r"(wg|wu|w1)/(w|b)$",   P(None, None, "model")),
        (r"(wd|w2)/w$",          P(None, "model", None)),
        (r"(wd|w2)/b$",          P(None, None)),
        (r"experts/w[gu1]/(w|b)$", P(None, None, None, "model")),
        (r"experts/wd/w$",       P(None, None, "model", None)),
        (r"shared/w[gu1]/(w|b)$", P(None, None, None, "model")),
        (r"shared/wd/w$",        P(None, None, "model", None)),
        (r"mx/w_in/w$",          P(None, None, "model")),
        (r"mx/w_x/w$",           P(None, "model", None)),
        (r"mx/w_out/w$",         P(None, "model", None)),
        (r"mx/conv_w$",          P(None, None, "model")),
        (r"mx/conv_b$",          P(None, "model")),
        (r"mx/w_dt$",            P(None, None, "model")),
        (r"mx/(dt_bias|D)$",     P(None, "model")),
        (r"mx/A_log$",           P(None, "model", None)),
        (r"mx/w[qkv]/w$",        P(None, None, "model")),
        (r"mx/wo/w$",            P(None, "model", None)),
        (r"mx/w_[zifo]/w$",      P(None, None, "model")),
        (r"lm_head/w$",          P(None, "model")),
        (r"lm_head/w$",          P(None, "model")),
    ]


def _spec_for(path: str, rules, ndim: int) -> P:
    for rx, spec in rules:
        if re.search(rx, path):
            parts = list(spec)
            # pad/trim to rank (stacked multi-head lm_head etc.)
            while len(parts) < ndim:
                parts.insert(0, None)
            return P(*parts[:ndim])
    return P()  # replicate


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim (explicit pjit
    shardings require exact divisibility — e.g. batch 1 at long_500k, or
    4-head gate tensors vs a 16-way model axis)."""
    if shape is None:
        return spec
    parts = []
    for i, ax in enumerate(spec):
        if ax is None or i >= len(shape):
            parts.append(None if i >= len(shape) else ax)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        keep = []
        rem = shape[i]
        for a in axes:
            n = mesh.shape[a]
            if rem % n == 0:
                keep.append(a)
                rem //= n
        parts.append(tuple(keep) if len(keep) > 1 else
                     (keep[0] if keep else None))
    return P(*parts[:len(shape)])


def _tree_paths_specs(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    def path_str(kp):
        out = []
        for k in kp:
            if hasattr(k, "key"):
                out.append(str(k.key))
            elif hasattr(k, "idx"):
                out.append(str(k.idx))
            elif hasattr(k, "name"):
                out.append(str(k.name))
        return "/".join(out)

    return [(path_str(kp), v) for kp, v in flat]


def make_param_shardings(mesh: Mesh, tree, *, mode: str = "qat",
                         fsdp: bool = True):
    """Pytree of NamedShardings matching ``tree`` (works on ShapeDtypeStructs)."""
    dp = "data"
    fs = ("pod", "data") if ("pod" in mesh.axis_names and fsdp) else (
        "data" if fsdp else None)
    rules = _qat_rules(dp, fs) if mode == "qat" else _serve_rules(dp)
    leaves = _tree_paths_specs(tree)
    specs = []
    for p, v in leaves:
        # quantized-moment NamedTuples flatten to <param>/codes (shaped like
        # the param) and <param>/scale (per-slice scales -> replicate)
        if p.endswith("/scale") or p.endswith("/1"):
            specs.append(P())
            continue
        if p.endswith("/codes"):
            p = p[: -len("/codes")]
        elif p.endswith("/0"):
            p = p[:-2]
        sp = _spec_for(p, rules, getattr(v, "ndim", 0))
        specs.append(_fit_spec(sp, getattr(v, "shape", None), mesh))
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, s) for s in specs])


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_sharding(mesh: Mesh, ndim: int, shape=None) -> NamedSharding:
    spec = P(batch_axes(mesh), *([None] * (ndim - 1)))
    return NamedSharding(mesh, _fit_spec(spec, shape, mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def cache_sharding(mesh: Mesh, tree):
    """KV/SSM cache: (n_reps, B, ...) -> batch over DP axes; int8 K/V shard
    head_dim over model (works for every GQA config; scores psum once)."""
    dp = batch_axes(mesh)

    def spec(path, v):
        nd = v.ndim
        if path.endswith("/k") or path.endswith("/v"):
            sp = P(None, dp, None, None, "model")     # (L,B,S,Hkv,hd)
        elif nd >= 2:
            sp = P(None, dp, *([None] * (nd - 2)))
        else:
            sp = P()
        return _fit_spec(sp, v.shape, mesh)

    leaves = _tree_paths_specs(tree)
    specs = [spec(p, v) for p, v in leaves]
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, s) for s in specs])


# --- TP-sharded paged KV pool ---------------------------------------------------

def kv_pool_pspec() -> P:
    """PartitionSpec of one paged-pool K/V leaf (n_reps, n_pages, Hkv, P, hd):
    KV heads shard over the model axis, everything else — crucially the PAGE
    axis — stays unsharded.  Page ids are therefore global: every rank holds
    its heads' slice of EVERY page, so one host-side block table / allocator
    decision addresses all ranks identically and spill/restore never moves
    data across ranks."""
    return P(None, None, "model", None, None)


def paged_pool_shardings(mesh: Mesh, tree):
    """Pytree of NamedShardings for the paged KV pool (``init_paged_cache``
    output): every k/v leaf sharded per ``kv_pool_pspec``.  Axes that do not
    divide (Hkv % tp != 0) are dropped by ``_fit_spec`` — callers that
    require a real shard must assert divisibility themselves (the serving
    engine does)."""
    leaves = _tree_paths_specs(tree)
    specs = [_fit_spec(kv_pool_pspec(), v.shape, mesh) for _, v in leaves]
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, s) for s in specs])


def kv_pool_specs(tree, mesh: Mesh):
    """Pytree of PartitionSpecs (not NamedShardings) mirroring the paged
    cache — the ``in_specs``/``out_specs`` form for a shard_map over the
    pool.  5-D payload leaves take ``kv_pool_pspec`` (Hkv over the model
    axis); lower-rank leaves — the packed pool's per-page (n_reps, n_pages)
    scale leaves — are replicated, since scales are derived from FULL-head
    codes and every rank holds all of them."""
    def spec(v):
        if v.ndim == 5:
            return _fit_spec(kv_pool_pspec(), v.shape, mesh)
        return P(*([None] * v.ndim))
    return jax.tree.map(spec, tree)


# --- activation-constraint context ---------------------------------------------

_CTX = threading.local()


def set_mesh_ctx(mesh: Optional[Mesh]):
    _CTX.mesh = mesh


def get_mesh_ctx() -> Optional[Mesh]:
    return getattr(_CTX, "mesh", None)


def constrain(x, *spec) -> jax.Array:
    """with_sharding_constraint that no-ops when no mesh context is set
    (keeps model code runnable in plain single-device tests) and silently
    drops axes that don't divide the corresponding dim."""
    mesh = get_mesh_ctx()
    if mesh is None:
        return x
    fitted = _fit_spec(P(*spec), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


def dp_axes_or_none():
    mesh = get_mesh_ctx()
    if mesh is None:
        return None
    return batch_axes(mesh)


def model_axis_size() -> int:
    mesh = get_mesh_ctx()
    if mesh is None or "model" not in mesh.axis_names:
        return 0
    return mesh.shape["model"]

"""Config-driven model stack: embeddings/frontend -> scanned super-blocks ->
final norm -> LM head(s).  Covers all ten assigned architectures + BERT.

Depth is handled by ``lax.scan`` over the repeating super-block (pattern), so
HLO size is O(1) in n_layers — a 126-layer 405B model lowers as fast as a
2-layer smoke model.  Params and amax-EMA state are stacked (n_reps, ...) on
the leading axis; per-rep quantization observations come back as scan ys.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as Mb
from repro.models import moe as Moe
from repro.models import xlstm as Xl

# ---------------------------------------------------------------------------
# slot descriptors
# ---------------------------------------------------------------------------

ATTN_SITES = ("attn_in", "q_pre", "k_pre", "q", "k", "v", "attn_out_in",
              "resid_a")
MLP_SITES_SWIGLU = ("mlp_in", "g_pre", "g_out", "u_out", "h_in", "resid_m")
MLP_SITES_GELU = ("mlp_in", "h_pre", "g_out", "h_in", "resid_m")


def slot_kinds(cfg: ModelConfig):
    """[(mixer, ffn)] per slot in the super-block pattern."""
    out = []
    for i, blk in enumerate(cfg.pattern):
        mixer = {"a": "attn", "m": "mamba", "s": "slstm", "x": "mlstm"}[blk]
        if cfg.family == "ssm":
            ffn = "none"
        elif cfg.n_experts and i % cfg.moe_period == cfg.moe_offset:
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "dense"
        else:
            ffn = "none"
        out.append((mixer, ffn))
    return out


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def _norm_params(cfg, dim=None):
    d = dim or cfg.d_model
    p = {"gamma": jnp.ones((d,), cfg.dtype)}
    if cfg.norm_type == "layernorm":
        p["beta"] = jnp.zeros((d,), cfg.dtype)
    return p


def _dense(key, din, dout, cfg, scale=0.02):
    return (jax.random.normal(key, (din, dout)) * scale).astype(cfg.dtype)


def init_slot_params(cfg: ModelConfig, mixer: str, ffn: str, key) -> Dict:
    ks = iter(jax.random.split(key, 24))
    d, hd = cfg.d_model, cfg.hd
    p: Dict = {"norm1": _norm_params(cfg)}
    if mixer == "attn":
        p["attn"] = {
            "wq": _dense(next(ks), d, cfg.n_heads * hd, cfg),
            "wk": _dense(next(ks), d, cfg.n_kv_heads * hd, cfg),
            "wv": _dense(next(ks), d, cfg.n_kv_heads * hd, cfg),
            "wo": _dense(next(ks), cfg.n_heads * hd, d, cfg),
        }
        if cfg.learned_pos:  # BERT uses biases everywhere
            p["attn"].update(
                bq=jnp.zeros((cfg.n_heads * hd,), cfg.dtype),
                bk=jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype),
                bv=jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype),
                bo=jnp.zeros((d,), cfg.dtype))
        if cfg.qk_norm:
            p["attn"]["qn"] = jnp.ones((hd,), cfg.dtype)
            p["attn"]["kn"] = jnp.ones((hd,), cfg.dtype)
    elif mixer == "mamba":
        d_in, dt_rank = Mb.mamba_dims(cfg)
        n = cfg.mamba_d_state
        p["mixer"] = {
            "w_in": _dense(next(ks), d, 2 * d_in, cfg),
            "conv_w": (jax.random.normal(next(ks), (cfg.mamba_d_conv, d_in))
                       * 0.1).astype(cfg.dtype),
            "conv_b": jnp.zeros((d_in,), cfg.dtype),
            "w_x": _dense(next(ks), d_in, dt_rank + 2 * n, cfg),
            "w_dt": _dense(next(ks), dt_rank, d_in, cfg, scale=dt_rank**-0.5),
            "dt_bias": jnp.full((d_in,), -4.6, jnp.float32),  # softplus^-1(0.01)
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32), (d_in, n))),
            "D": jnp.ones((d_in,), jnp.float32),
            "w_out": _dense(next(ks), d_in, d, cfg),
        }
    elif mixer == "mlstm":
        p["mixer"] = {
            "wq": _dense(next(ks), d, d, cfg),
            "wk": _dense(next(ks), d, d, cfg),
            "wv": _dense(next(ks), d, d, cfg),
            "wo": _dense(next(ks), d, d, cfg),
            "w_ig": _dense(next(ks), d, cfg.n_heads, cfg),
            "b_ig": jnp.zeros((cfg.n_heads,), jnp.float32),
            "w_fg": _dense(next(ks), d, cfg.n_heads, cfg),
            "b_fg": jnp.full((cfg.n_heads,), 3.0, jnp.float32),
            "w_og": _dense(next(ks), d, d, cfg),
            "b_og": jnp.zeros((d,), cfg.dtype),
            "ln_y": jnp.ones((d,), cfg.dtype),
        }
    elif mixer == "slstm":
        dh = d // cfg.n_heads
        p["mixer"] = {
            "w_z": _dense(next(ks), d, d, cfg), "b_z": jnp.zeros((d,), cfg.dtype),
            "w_i": _dense(next(ks), d, d, cfg), "b_i": jnp.zeros((d,), cfg.dtype),
            "w_f": _dense(next(ks), d, d, cfg), "b_f": jnp.full((d,), 3.0, cfg.dtype),
            "w_o": _dense(next(ks), d, d, cfg), "b_o": jnp.zeros((d,), cfg.dtype),
            "r": (jax.random.normal(next(ks), (cfg.n_heads, dh, 4 * dh))
                  * dh**-0.5).astype(cfg.dtype),
            "w_out": _dense(next(ks), d, d, cfg),
        }
    if ffn != "none":
        p["norm2"] = _norm_params(cfg)
    if ffn == "dense":
        if cfg.act == "swiglu":  # noqa: SIM108 - parallel dict literals
            p["mlp"] = {
                "wg": _dense(next(ks), d, cfg.d_ff, cfg),
                "wu": _dense(next(ks), d, cfg.d_ff, cfg),
                "wd": _dense(next(ks), cfg.d_ff, d, cfg),
            }
        else:
            p["mlp"] = {
                "w1": _dense(next(ks), d, cfg.d_ff, cfg),
                "b1": jnp.zeros((cfg.d_ff,), cfg.dtype),
                "w2": _dense(next(ks), cfg.d_ff, d, cfg),
                "b2": jnp.zeros((d,), cfg.dtype),
            }
    elif ffn == "moe":
        fe = cfg.moe_d_ff or cfg.d_ff
        p["moe"] = {
            "router": _dense(next(ks), d, cfg.n_experts, cfg),
            "experts": {
                "wg": _dense(next(ks), cfg.n_experts * d, fe, cfg).reshape(
                    cfg.n_experts, d, fe),
                "wu": _dense(next(ks), cfg.n_experts * d, fe, cfg).reshape(
                    cfg.n_experts, d, fe),
                "wd": _dense(next(ks), cfg.n_experts * fe, d, cfg).reshape(
                    cfg.n_experts, fe, d),
            },
        }
        if cfg.n_shared_experts:
            p["moe"]["shared"] = {
                "wg": _dense(next(ks), cfg.n_shared_experts * d, fe, cfg
                             ).reshape(cfg.n_shared_experts, d, fe),
                "wu": _dense(next(ks), cfg.n_shared_experts * d, fe, cfg
                             ).reshape(cfg.n_shared_experts, d, fe),
                "wd": _dense(next(ks), cfg.n_shared_experts * fe, d, cfg
                             ).reshape(cfg.n_shared_experts, fe, d),
            }
    return p


def slot_sites(cfg: ModelConfig, mixer: str, ffn: str):
    sites = []
    if mixer == "attn":
        sites += list(ATTN_SITES)
    elif mixer == "mamba":
        sites += list(Mb.MAMBA_SITES) + ["resid_a"]
    elif mixer == "mlstm":
        sites += list(Xl.MLSTM_SITES) + ["resid_a"]
    elif mixer == "slstm":
        sites += list(Xl.SLSTM_SITES) + ["resid_a"]
    if ffn == "dense":
        sites += list(MLP_SITES_SWIGLU if cfg.act == "swiglu" else MLP_SITES_GELU)
    elif ffn == "moe":
        sites += list(Moe.MOE_SITES) + ["resid_m"]
        if cfg.n_shared_experts:
            sites += list(Moe.MOE_SHARED_SITES)
    return sites


def init_params(cfg: ModelConfig, key) -> Dict:
    kinds = slot_kinds(cfg)
    keys = jax.random.split(key, len(kinds) * cfg.n_reps + 4)
    blocks = {}
    for i, (mixer, ffn) in enumerate(kinds):
        # one batched init over the rep keys: the same values as stacking
        # per-rep inits (random sampling is vmap-consistent), without a
        # second copy of every stacked leaf or an n_reps-times unrolled
        # program when jitted
        rep_keys = keys[i * cfg.n_reps:(i + 1) * cfg.n_reps]
        blocks[f"slot{i}"] = jax.vmap(
            lambda k, mixer=mixer, ffn=ffn: init_slot_params(cfg, mixer, ffn,
                                                             k))(rep_keys)
    p = {
        "embed": {"tokens": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                             * 0.02).astype(cfg.dtype)},
        "blocks": blocks,
        "final_norm": _norm_params(cfg),
    }
    if cfg.frontend == "audio_codebooks":
        p["embed"]["codebooks"] = (jax.random.normal(
            keys[-2], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(cfg.dtype)
    if cfg.learned_pos:
        p["embed"]["pos"] = (jax.random.normal(
            keys[-3], (cfg.max_position, cfg.d_model)) * 0.02).astype(cfg.dtype)
    if not cfg.tied_embeddings:
        heads = cfg.n_lm_heads
        shape = (heads, cfg.d_model, cfg.vocab_size) if heads > 1 else (
            cfg.d_model, cfg.vocab_size)
        p["lm_head"] = (jax.random.normal(keys[-4], shape) * 0.02).astype(cfg.dtype)
    return p


def init_amax(cfg: ModelConfig) -> Dict:
    kinds = slot_kinds(cfg)
    blocks = {}
    for i, (mixer, ffn) in enumerate(kinds):
        blocks[f"slot{i}"] = {s: jnp.zeros((cfg.n_reps,), jnp.float32)
                              for s in slot_sites(cfg, mixer, ffn)}
    return {"blocks": blocks,
            "embed_out": jnp.zeros((), jnp.float32),
            "head_in": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

CHUNK_TOKENS = 4096  # token-chunk size for the (token-parallel) dense MLP


def _chunked_mlp(x, p, amax, policy, act):
    """Token-chunked QAT MLP: rows are independent, so scanning token chunks
    caps the live (tokens, d_ff) fake-quant chain at CHUNK_TOKENS rows —
    this is what keeps the train_4k backward inside HBM."""
    b, s, d = x.shape
    c = 512  # seq-chunk per batch element: keeps the dp sharding of B intact
    if b * s <= 2 * CHUNK_TOKENS or s % c != 0 or s <= c:
        return L.mlp_qat(x, p, amax, policy, act)
    xt = x.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)   # (nc, B, c, d)

    def body(_, xc):
        y, o = L.mlp_qat(xc, p, amax, policy, act)
        return None, (y, o)

    body = jax.checkpoint(body)
    _, (ys, obs_c) = jax.lax.scan(body, None, xt)
    obs = jax.tree.map(lambda t: jnp.max(t, axis=0), obs_c)
    return ys.transpose(1, 0, 2, 3).reshape(b, s, d), obs


def _apply_slot(cfg, mixer, ffn, x, p, amax, pos, mask):
    policy = cfg.quant
    obs: Dict = {}
    h = L.qnorm(x, p["norm1"], policy, cfg.norm_type)
    if mixer == "attn":
        out, o = L.attention_qat(h, p["attn"], amax, policy, cfg, pos, mask)
    elif mixer == "mamba":
        out, o, _ = Mb.mamba_qat(h, p["mixer"], amax, policy, cfg)
    elif mixer == "mlstm":
        out, o, _ = Xl.mlstm_qat(h, p["mixer"], amax, policy, cfg)
    else:
        out, o, _ = Xl.slstm_qat(h, p["mixer"], amax, policy, cfg)
    obs.update(o)
    x, obs["resid_a"] = L.residual_add(x, out, amax["resid_a"], policy)
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h = L.qnorm(x, p["norm2"], policy, cfg.norm_type)
        if ffn == "dense":
            out, o = _chunked_mlp(h, p["mlp"], amax, policy, cfg.act)
        else:
            out, o, aux = Moe.moe_qat(h, p["moe"], amax, policy, cfg)
        obs.update(o)
        x, obs["resid_m"] = L.residual_add(x, out, amax["resid_m"], policy)
    return x, obs, aux


def forward(
    cfg: ModelConfig,
    params: Dict,
    amax: Dict,
    tokens: jax.Array,                   # (B, S) int32, or (B, K, S) audio
    *,
    mask: Optional[jax.Array] = None,    # (B, 1, S, S) bool; None -> causal
    pos: Optional[jax.Array] = None,
    extra_embeds: Optional[jax.Array] = None,  # vlm stub: (B, S_img, d)
    pos3: Optional[jax.Array] = None,          # vlm: (B, S, 3) M-RoPE ids
) -> Tuple[jax.Array, Dict, jax.Array]:
    """QAT forward.  Returns (logits, obs-tree matching init_amax, aux_loss)."""
    policy = cfg.quant
    # --- embed / frontend ---
    if cfg.frontend == "audio_codebooks":
        b, k, s = tokens.shape
        x = jnp.zeros((b, s, cfg.d_model), cfg.dtype)
        for ci in range(cfg.n_codebooks):
            x = x + jnp.take(params["embed"]["codebooks"][ci], tokens[:, ci], 0)
    else:
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s = x.shape[0], x.shape[1]
    if cfg.learned_pos:
        x = x + params["embed"]["pos"][None, :s]
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.mrope_sections is not None:
        if pos3 is None:
            pos3 = jnp.broadcast_to(pos[..., None], (*pos.shape, 3))
        pos = pos3
    x, obs_embed = L.fake_quant_act(x, amax["embed_out"], policy.a_bits,
                                    policy.quantize_wa)
    from repro.sharding import partition as Pt
    dp = Pt.dp_axes_or_none()
    if dp:
        x = Pt.constrain(x, dp, None, None)
    if mask is None and not cfg.causal:
        mask = jnp.ones((b, 1, s, s), bool)

    # --- scanned super-blocks ---
    kinds = slot_kinds(cfg)

    def body(carry, xs):
        xc, aux_sum = carry
        p_rep, a_rep = xs
        obs_rep = {}
        for i, (mixer, ffn) in enumerate(kinds):
            xc, o, aux = _apply_slot(cfg, mixer, ffn, xc,
                                     p_rep[f"slot{i}"], a_rep[f"slot{i}"],
                                     pos, mask)
            obs_rep[f"slot{i}"] = o
            aux_sum = aux_sum + aux
        return (xc, aux_sum), obs_rep

    if cfg.remat:
        body = jax.checkpoint(body)
    carry0 = (x, jnp.zeros((), jnp.float32))
    g = cfg.remat_groups
    if g > 1 and cfg.n_reps % g == 0:
        # two-level (sqrt-L) checkpointing: residuals live only at group
        # boundaries; backward recomputes one group at a time.
        per = cfg.n_reps // g

        def regroup(t):
            return t.reshape(g, per, *t.shape[1:])

        xs_g = jax.tree.map(regroup, (params["blocks"], amax["blocks"]))

        def group_body(carry, xs):
            c, obs_g = jax.lax.scan(body, carry, xs)
            return c, obs_g

        group_body = jax.checkpoint(group_body)
        (x, aux_total), obs_nested = jax.lax.scan(group_body, carry0, xs_g)
        obs_blocks = jax.tree.map(
            lambda t: t.reshape(cfg.n_reps, *t.shape[2:]), obs_nested)
    else:
        (x, aux_total), obs_blocks = jax.lax.scan(
            body, carry0, (params["blocks"], amax["blocks"]))

    # --- head ---
    x = L.qnorm(x, params["final_norm"], policy, cfg.norm_type)
    x, obs_head = L.fake_quant_act(x, amax["head_in"], policy.a_bits,
                                   policy.quantize_wa)
    if cfg.tied_embeddings:
        w = params["embed"]["tokens"].T
        logits = x @ w
    else:
        w = params["lm_head"]
        logits = (jnp.einsum("bsd,kdv->bksv", x, w)
                  if cfg.n_lm_heads > 1 else x @ w)
    obs = {"blocks": obs_blocks, "embed_out": obs_embed, "head_in": obs_head}
    return logits.astype(jnp.float32), obs, aux_total

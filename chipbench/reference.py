"""Plain float32 reference of a W4A8 decoder LM, and its lower-precision
control.  Imports nothing of the program.

It is the configuration's arithmetic written out once, straightforwardly,
in ``jax.numpy`` at ``highest`` matmul precision: pre-norm decoder layers
(RMSNorm or LayerNorm, optional per-head RMS q/k-norm, split-half rotary
embedding on a leading fraction of each head, grouped-query causal softmax
attention, SwiGLU MLP), with the quantization the configuration states:

* weights: symmetric per-tensor int4 codes; a linear layer's weight scale
  carries 8 significant bits, the output head's is exact;
* activations: symmetric per-tensor int8 at static scales (8 significant
  bits) observed once on a calibration batch, at the embedding output, the
  norm outputs that feed a matmul, the q/k projections before and after
  rotation, v, the attention context, the residual after each sublayer, the
  gate before and after SiLU, the up projection and the gated product; q, k
  and v at int8 are the KV cache the configuration states.

Softmax, norms, SiLU and rotation are exact float32: the program's lookup
tables and integer norms are what is under test.  ``a_bits=4`` is the
control: the same arithmetic with every activation site (the KV cache with
it) at int4, the nearest precision below the configuration's int8.

The reference runs once per sampled request over the prompt and the served
tokens (teacher forcing), in query blocks, layer by layer under a scan, so
that it fits beside its float weights on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LAYER_SITES = ("attn_in", "q_pre", "k_pre", "q", "k", "v", "attn_out_in",
               "resid_a", "mlp_in", "g_pre", "g_out", "u_out", "h_in",
               "resid_m")
QBLOCK = 256          # query rows per attention block


def qmax(bits: int) -> float:
    return float((1 << (bits - 1)) - 1)


def scale8(s):
    """``s`` rounded to 8 significant bits."""
    e = jnp.floor(jnp.log2(jnp.maximum(s, 1e-30))) - 7.0
    return jnp.round(s * jnp.exp2(-e)) * jnp.exp2(e)


def quant_weight(w, bits: int = 4, round_scale: bool = True):
    """Per-tensor symmetric ``bits`` codes of ``w``, dequantized (float32)."""
    w = w.astype(jnp.float32)
    s = qmax(bits) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
    if round_scale:
        s = scale8(s)
    return jnp.clip(jnp.round(w * s), -qmax(bits), qmax(bits)) / s


def quant_act(x, amax, bits: int):
    """Per-tensor symmetric ``bits`` codes of ``x`` at the static range
    ``amax``, dequantized."""
    s = scale8(qmax(bits) / jnp.maximum(amax, 1e-8))
    return jnp.clip(jnp.round(x * s), -qmax(bits), qmax(bits)) / s


class _Sites:
    """Activation quantizer over named sites.  With ``amax`` None it
    observes (calibration: each site quantized at its own observed range);
    otherwise it quantizes at the given static ranges."""

    def __init__(self, amax, bits):
        self.amax, self.bits, self.obs = amax, bits, {}

    def __call__(self, name, x):
        ob = jnp.max(jnp.abs(x))
        self.obs[name] = ob
        a = ob if self.amax is None else self.amax[name]
        return quant_act(x, a, self.bits)


def _norm(x, p, m):
    if m["norm_type"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        c = x - mu
        n = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                              + m["norm_eps"])
        return n * p["gamma"].astype(jnp.float32) + \
            p["beta"].astype(jnp.float32)
    n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["norm_eps"])
    return n * p["gamma"].astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def _rope(x, pos, m):
    """x (S, H, D); split-half rotation of the first ``partial_rotary``
    fraction of D."""
    d = x.shape[-1]
    d_rot = int(d * m["partial_rotary"])
    d_rot -= d_rot % 2
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    freqs = 1.0 / (m["rope_theta"] ** (
        jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xp], -1)


def _attention(q, k, v, m):
    """Causal softmax attention, q (S, H, D), k/v (S, Hkv, D), in blocks of
    QBLOCK query rows (one block where S is not a multiple of QBLOCK)."""
    s, nh, hd = q.shape
    qb = QBLOCK if s % QBLOCK == 0 else s
    group = nh // k.shape[1]
    kg = jnp.repeat(k, group, axis=1)
    vg = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        sc = jnp.einsum("qhd,khd->hqk", qs, kg) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vg)

    out = jax.lax.map(block, jnp.arange(s // qb))
    return out.reshape(s, nh, hd)


def _layer(x, p, pos, m, sites):
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    s = x.shape[0]
    a = p["attn"]
    h = sites("attn_in", _norm(x, p["norm1"], m))
    q = sites("q_pre", (h @ quant_weight(a["wq"])).reshape(s, nh, hd))
    k = sites("k_pre", (h @ quant_weight(a["wk"])).reshape(s, nkv, hd))
    v = sites("v", (h @ quant_weight(a["wv"])).reshape(s, nkv, hd))
    if m["qk_norm"]:
        q = _rms(q, a["qn"], m["norm_eps"])
        k = _rms(k, a["kn"], m["norm_eps"])
    q = sites("q", _rope(q, pos, m))
    k = sites("k", _rope(k, pos, m))
    ctx = sites("attn_out_in", _attention(q, k, v, m).reshape(s, nh * hd))
    x = sites("resid_a", x + ctx @ quant_weight(a["wo"]))
    mp = p["mlp"]
    h = sites("mlp_in", _norm(x, p["norm2"], m))
    g = sites("g_pre", h @ quant_weight(mp["wg"]))
    u = sites("u_out", h @ quant_weight(mp["wu"]))
    g = sites("g_out", jax.nn.silu(g))
    hh = sites("h_in", g * u)
    return sites("resid_m", x + hh @ quant_weight(mp["wd"]))


def _hidden(params, m, tokens, amax, bits):
    """Final-norm output (S, d) for one sequence, and the observed ranges."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    top = _Sites(amax, bits)
    x = top("embed_out",
            jnp.take(params["embed"]["tokens"], tokens, 0).astype(jnp.float32))

    def body(x, xs):
        p, a = xs
        sites = _Sites(a, bits)
        x = _layer(x, p, pos, m, sites)
        return x, {n: sites.obs[n] for n in LAYER_SITES}

    blocks = params["blocks"]["slot0"]
    if amax is None:          # calibration: every layer observes its own
        x, obs = jax.lax.scan(lambda x, p: body(x, (p, None)), x, blocks)
    else:
        x, obs = jax.lax.scan(body, x, (blocks, amax["layers"]))
    h = top("head_in", _norm(x, params["final_norm"], m))
    return h, {"embed_out": top.obs["embed_out"],
               "head_in": top.obs["head_in"], "layers": obs}


def _head(params, m):
    w = (params["embed"]["tokens"].T if m["tied_embeddings"]
         else params["lm_head"])
    return quant_weight(w, round_scale=False)


@functools.partial(jax.jit, static_argnums=(1, 3))
def calibrate(params, m_items, tokens, bits):
    """Static activation ranges: each site's largest magnitude over the
    calibration batch (B, S), observed with every earlier site quantized at
    its own observed range."""
    m = dict(m_items)
    with jax.default_matmul_precision("highest"):
        obs = jax.vmap(lambda t: _hidden(params, m, t, None, bits)[1])(tokens)
    return jax.tree.map(lambda x: jnp.max(x, 0), obs)


@functools.partial(jax.jit, static_argnums=(1, 5))
def score(params, m_items, tokens, targets, amax, bits, ctrl_amax=None):
    """One teacher-forced sequence.  ``tokens`` (S,) padded to a multiple
    of QBLOCK; ``targets`` (S,) the token served after each position.
    Returns per position: the reference's best logit, its logit of the
    served token, and, with ``ctrl_amax``, its logit of the token the int4
    control ranks first."""
    m = dict(m_items)
    with jax.default_matmul_precision("highest"):
        h, _ = _hidden(params, m, tokens, amax, bits)
        w = _head(params, m)
        hc = (None if ctrl_amax is None
              else _hidden(params, m, tokens, ctrl_amax, 4)[0])

        def rows(i):
            hb = jax.lax.dynamic_slice_in_dim(h, i * QBLOCK, QBLOCK, 0)
            tb = jax.lax.dynamic_slice_in_dim(targets, i * QBLOCK, QBLOCK, 0)
            lg = hb @ w
            best = jnp.max(lg, -1)
            served = jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]
            if hc is None:
                return best, served, served
            cb = jax.lax.dynamic_slice_in_dim(hc, i * QBLOCK, QBLOCK, 0)
            ctok = jnp.argmax(cb @ w, -1)
            ctrl = jnp.take_along_axis(lg, ctok[:, None], -1)[:, 0]
            return best, served, ctrl

        best, served, ctrl = jax.lax.map(
            rows, jnp.arange(tokens.shape[0] // QBLOCK))
    return best.reshape(-1), served.reshape(-1), ctrl.reshape(-1)


def model_items(m: dict) -> tuple:
    """The sizes the reference reads, as a hashable static argument."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "qk_norm", "norm_type", "norm_eps",
            "partial_rotary", "rope_theta", "tied_embeddings")
    return tuple((k, m[k]) for k in keys)

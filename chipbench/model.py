"""The benchmark's model inputs: seeded float weights and calibration tokens.

The weights are the benchmark's input, made here from ``--seed`` in one
jitted call on the device, in the parameter layout the program's
calibration forward and fold read (``params["blocks"]["slot0"]...``,
stacked over layers).  The plain reference (``reference.py``) is given the
same float weights, never anything the program computed from them.

The distribution is chosen so that a W4A8 integer model has something to
get right: token embeddings of unit scale, attention with peaked scores
(score std about 2.25, so the context really depends on the keys read), and
per-layer residual increments small enough that the residual stream stays
inside the embedding's int8 grid, which the program uses for the residual
between layers.  Every width is the configuration's own.
"""
from __future__ import annotations

import numpy as np

# residual increments per layer, as a multiple of a unit-scale input
ATTN_OUT_GAIN = 0.2       # wo std = gain / sqrt(n_heads * head_dim)
MLP_OUT_GAIN = 0.15       # wd std = gain / sqrt(d_ff)
QK_GAIN = 1.5             # q and k elements of std 1.5: score std 2.25


def jax_key(seed: int, stream: int):
    """A JAX PRNG key for ``stream`` of a run seeded with ``seed`` (any
    non-negative integer, larger than 32 bits included)."""
    import jax
    word = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _layer_shapes(m: dict):
    d, hd = m["d_model"], m["head_dim"]
    nh, nkv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    return d, hd, nh, nkv, ff


def make_params(m: dict, seed: int):
    """Float weights for model sizes ``m`` (a configuration's ``model``
    block), bfloat16, on the default device, from one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        d, hd, nh, nkv, ff = _layer_shapes(m)
        n_layers, vocab = m["n_layers"], m["vocab_size"]
        ks = iter(jax.random.split(key, 16))
        bf = jnp.bfloat16

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(bf)

        L = n_layers
        qk = QK_GAIN if not m["qk_norm"] else 1.0
        attn = {
            "wq": normal((L, d, nh * hd), qk / np.sqrt(d)),
            "wk": normal((L, d, nkv * hd), qk / np.sqrt(d)),
            "wv": normal((L, d, nkv * hd), 1.0 / np.sqrt(d)),
            "wo": normal((L, nh * hd, d), ATTN_OUT_GAIN / np.sqrt(nh * hd)),
        }
        if m["qk_norm"]:
            attn["qn"] = jnp.full((L, hd), QK_GAIN, bf)
            attn["kn"] = jnp.full((L, hd), QK_GAIN, bf)

        def norm(*lead):
            p = {"gamma": jnp.ones((*lead, d), bf)}
            if m["norm_type"] == "layernorm":
                p["beta"] = jnp.zeros((*lead, d), bf)
            return p

        slot = {
            "norm1": norm(L), "attn": attn, "norm2": norm(L),
            "mlp": {"wg": normal((L, d, ff), 1.0 / np.sqrt(d)),
                    "wu": normal((L, d, ff), 1.0 / np.sqrt(d)),
                    "wd": normal((L, ff, d), MLP_OUT_GAIN / np.sqrt(ff))},
        }
        params = {"embed": {"tokens": normal((vocab, d), 1.0)},
                  "blocks": {"slot0": slot}, "final_norm": norm()}
        if m["tied_embeddings"]:
            return params
        params["lm_head"] = normal((d, vocab), 1.0 / np.sqrt(d))
        return params

    return build(jax_key(seed, 0))


def calib_tokens(m: dict, seed: int):
    """The calibration batch the program's activation scales are observed
    on (and the reference's own, independently)."""
    rng = np.random.default_rng([seed, 1])
    b, s = m["calib_shape"]
    return rng.integers(0, m["vocab_size"], (b, s)).astype(np.int32)


def program_config(m: dict):
    """The program's ``ModelConfig`` for these sizes.  Every size is checked
    against the registered configuration, so the file and the program cannot
    disagree unseen."""
    import dataclasses

    from repro.configs import get_config
    cfg = get_config(m["program_config"])
    want = dict(n_layers=m["n_layers"], d_model=m["d_model"],
                n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                head_dim=m["head_dim"], d_ff=m["d_ff"],
                vocab_size=m["vocab_size"], qk_norm=m["qk_norm"],
                norm_type=m["norm_type"], partial_rotary=m["partial_rotary"],
                rope_theta=m["rope_theta"],
                tied_embeddings=m["tied_embeddings"])
    if m.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **m["program_overrides"])
    got = {k: getattr(cfg, k) for k in want}
    if cfg.hd != m["head_dim"]:
        got["head_dim"] = cfg.hd
    diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if diff:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"benchmark's file: {diff}")
    return cfg


def fold_on_device(cfg, params, calib):
    """The program's own set-up: one calibration forward, then the integer
    fold, each one jitted program (as ``launch.serve.calibrated_folded``
    does, with the benchmark's weights in place of the program's)."""
    import jax
    import jax.numpy as jnp

    from repro.models import fold as F
    from repro.models import transformer as T

    amax = T.init_amax(cfg)
    _, obs, _ = jax.jit(T.forward, static_argnums=0)(
        cfg, params, amax, jnp.asarray(calib))
    return jax.jit(F.fold_params, static_argnums=0)(cfg, params, obs)

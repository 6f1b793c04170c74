"""Integration tests: the dry-run launch path on a tiny host mesh, the
serving engine end-to-end, SWA ring-buffer decode, and the HLO cost parser.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.models import fold as F
from repro.models import serve_int as S
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def _mesh22():
    n = len(jax.devices())
    if n < 4:
        pytest.skip("needs >=4 host devices (run under the dryrun env)")
    return jax.make_mesh((2, 2), ("data", "model"))


def test_lower_train_smoke_mesh():
    """The real dryrun lower_train path on a 2x2 mesh with a smoke config:
    proves the sharding rules + step builder compile end-to-end in-test."""
    from repro.launch.dryrun import lower_train
    from repro.sharding import partition as Pt

    mesh = _mesh22()
    cfg = smoke_config("yi-6b", param_dtype="bfloat16")
    shape = ShapeConfig("t", 64, 4, "train")
    Pt.set_mesh_ctx(mesh)
    try:
        lowered = lower_train(cfg, shape, mesh, fsdp=True, accum_steps=2)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
    finally:
        Pt.set_mesh_ctx(None)


def test_lower_serve_decode_smoke_mesh():
    from repro.launch.dryrun import lower_serve
    from repro.sharding import partition as Pt

    mesh = _mesh22()
    cfg = smoke_config("yi-6b")
    shape = ShapeConfig("d", 64, 4, "decode")
    Pt.set_mesh_ctx(mesh)
    try:
        compiled = lower_serve(cfg, shape, mesh).compile()
        assert "while" in compiled.as_text()
    finally:
        Pt.set_mesh_ctx(None)


def test_engine_generates_and_is_deterministic():
    from repro.serve.engine import Engine, EngineConfig, Request

    cfg = smoke_config("yi-6b")
    params = T.init_params(cfg, KEY)
    amax = T.init_amax(cfg)
    calib = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    _, obs, _ = T.forward(cfg, params, amax, calib)
    folded = F.fold_params(cfg, params, obs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(2)]

    def run():
        eng = Engine(cfg, folded, EngineConfig(batch_slots=2, max_len=64))
        reqs = [Request(prompt=p.copy(), max_new_tokens=5) for p in prompts]
        return [r.out.tolist() for r in eng.generate(reqs)]

    a, b = run(), run()
    assert a == b                       # greedy decode is deterministic
    assert all(len(o) == 5 for o in a)


@pytest.mark.slow
def test_swa_ring_buffer_decode_matches_prefill_tail():
    """Mixtral-style SWA: decode past the window via the ring buffer must
    agree with a windowed prefill on the same tokens."""
    cfg = smoke_config("mixtral-8x22b", sliding_window=8, n_layers=1)
    params = T.init_params(cfg, KEY)
    amax = T.init_amax(cfg)
    toks = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    _, obs, _ = T.forward(cfg, params, amax, toks)
    folded = F.fold_params(cfg, params, obs)
    cache = S.init_cache(cfg, 1, 64)    # ring size = window = 8
    assert cache["slot0"]["k"].shape[2] == 8
    outs = []
    for t in range(16):                 # decode 2x past the window
        lg, cache = S.serve_forward(cfg, folded, toks[:, t:t + 1], cache=cache,
                                    pos_offset=jnp.int32(t), mode="decode")
        outs.append(lg)
    dec = jnp.concatenate(outs, 1)
    pre, _ = S.serve_forward(cfg, folded, toks, mode="prefill")
    # compare the final position (full window context in both paths)
    pd = jax.nn.log_softmax(dec[:, -1], -1)
    pp = jax.nn.log_softmax(pre[:, -1], -1)
    p = jax.nn.softmax(pre[:, -1], -1)
    kl = float(jnp.sum(p * (pp - pd), -1).mean())
    assert np.isfinite(kl) and kl < 0.02


def test_hlo_cost_parser_scales_loops():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import hlo_cost

    hlo = """
HloModule test

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), to_apply=%add.red
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

%add.red (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (w: f32[8,8]) -> (s32[], f32[8,8]) {
  %w = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tu = (s32[], f32[8,8]) tuple(%z, %w)
  ROOT %wh = (s32[], f32[8,8]) while(%tu), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
}
"""
    out = hlo_cost.analyze(hlo)
    # dot flops: 2*8*8*8 = 1024 per trip x 10 trips
    assert out["dot_flops"] == 1024 * 10
    assert out["collectives"]["all-reduce"]["count"] == 10
    assert out["collectives"]["all-reduce"]["bytes"] == 8 * 8 * 4 * 10


def test_audio_engine_shapes():
    """musicgen serve path end-to-end at smoke scale (4 codebooks)."""
    cfg = smoke_config("musicgen-medium", n_layers=1)
    params = T.init_params(cfg, KEY)
    amax = T.init_amax(cfg)
    toks = jax.random.randint(KEY, (2, 4, 8), 0, cfg.vocab_size)
    _, obs, _ = T.forward(cfg, params, amax, toks)
    folded = F.fold_params(cfg, params, obs)
    cache = S.init_cache(cfg, 2, 16)
    lg, cache = S.serve_forward(cfg, folded, toks[:, :, :1], cache=cache,
                                pos_offset=jnp.int32(0), mode="decode")
    assert lg.shape == (2, cfg.n_codebooks, 1, cfg.vocab_size)


def test_vlm_loss_masks_image_positions():
    from repro.optim.adamw import AdamWConfig
    from repro.train import steps as St

    cfg = smoke_config("qwen2-vl-2b")
    opt = AdamWConfig(lr=1e-3)
    state = St.init_train_state(cfg, KEY, opt)
    b, n_img, s_txt = 2, 4, 12
    batch = {
        "tokens": jax.random.randint(KEY, (b, s_txt), 0, cfg.vocab_size),
        "extra_embeds": jax.random.normal(KEY, (b, n_img, cfg.d_model)),
        "pos3": jnp.broadcast_to(
            jnp.arange(n_img + s_txt, dtype=jnp.int32)[None, :, None],
            (b, n_img + s_txt, 3)),
    }
    step = jax.jit(St.make_train_step(cfg, opt))
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_chip_smoke_refuses_without_tpu(capsys, monkeypatch):
    """chip_smoke.py checks the chip only: without a TPU it exits non-zero
    and prints no result line (no silent CPU fallback)."""
    import importlib.util
    import sys
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main() != 0
    assert capsys.readouterr().out == ""


def test_compile_cache_placement(monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, with nothing set in code; otherwise to <checkout>/.jax_cache."""
    from pathlib import Path

    from repro.launch.compile_cache import configure_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

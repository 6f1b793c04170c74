"""The comparison that decides ``correct`` fails what it must, at smoke size
on the CPU: the int4 control in the program's place, and a run whose
served tokens are altered where they are produced."""
import jax
import pytest

from chipbench import run as RUN


@pytest.fixture
def no_compile_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: "off")


def test_control_fails_the_limit_the_program_passes(spec_of,
                                                    no_compile_cache):
    spec = spec_of("closed")
    limit = spec["cell"]["check"]["logit_gap_mean_max"]
    seed = 2**31 + 5
    run = RUN.measure(spec, seed, 2.0, False, jax.devices()[:1])
    got = RUN.check_outputs(spec, seed, run, 4, control=True)
    assert got["program"]["tokens"] >= 10
    assert got["program"]["mean"] <= limit
    assert got["control"]["mean"] > limit


def test_a_token_altered_where_it_is_produced_is_not_correct(
        spec_of, no_compile_cache):
    spec = spec_of("closed")
    vocab = spec["config"]["model"]["vocab_size"]

    def alter_every_seventh_token(eng):
        pick, n = eng._pick_token, [0]

        def altered(row, req):
            n[0] += 1
            tok = pick(row, req)
            return (tok + 1) % vocab if n[0] % 7 == 0 else tok

        eng._pick_token = altered

    res = RUN.run(spec, 2**31 + 6, 2.0, False, jax.devices()[:1],
                  fault=alter_every_seventh_token)
    assert res["correct"] is False
    gap = res["compared"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]

"""A serving cell's run, driven on the CPU at a tiny size with the timed
path sound and then broken underneath: a sound run is correct, a decode
that returns its state unchanged or a token altered where it is produced
makes ``correct`` false."""
import pytest

from conftest import serve_cell, tiny_cell


def test_sound_run_is_correct(cpu_run, tmp_path):
    res = cpu_run(tiny_cell(serve_cell(tmp_path)), seconds=2.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["ttft_p50_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_fault_makes_the_run_incorrect(cpu_run, monkeypatch, tmp_path,
                                       fault):
    import jax.numpy as jnp
    from repro.serve import engine
    from repro.train import steps
    if fault == "state_unchanged":
        real = steps.make_serve_step

        def broken(mc, **kw):
            step = real(mc, **kw)
            return lambda params, state, inputs: (
                step(params, state, inputs)[0], state)
        monkeypatch.setattr(steps, "make_serve_step", broken)
    else:
        real_sample = engine._sample

        def altered(logits, key, temperature, n):
            tok = real_sample(logits, key, temperature, n)
            return ((tok + 1) % logits.shape[-1]).astype(jnp.int32)
        monkeypatch.setattr(engine, "_sample", altered)
    res = cpu_run(tiny_cell(serve_cell(tmp_path)), seconds=2.0)
    assert not res["correct"], res["checks"]

"""The controls, at a size a test run holds: the plain reference put in
the program's place and computed one precision below the configuration's
fails the cell's limits."""
import jax

from conftest import serve_cell, tiny_cell

from bench.generators import lm_batches
from bench.lib import compare, runner


def _ctx(cell, seed):
    return runner.Ctx(cell, seed=seed, seconds=2.0, trace=False,
                      devices=jax.devices()[:1], t_process=0.0)


def test_training_control_bf16_is_not_correct():
    cell = tiny_cell("train.repro-100m.rq8ef")
    drv = cell.driver()
    for seed in (3, 4):
        ctx = _ctx(cell, seed)
        b = lm_batches.make(cell.traffic, cell.config["vocab_size"], seed)
        batches = [(x["tokens"], x["labels"]) for x in b[:3]]
        ref = drv.reference(ctx, batches)
        ctrl = drv.reference(ctx, batches, precision="bfloat16")
        numbers = drv.compare_with(ref, ctrl["losses"], ctrl["grad1"],
                                   ctrl["change"])
        numbers.pop("n_leaves_left_out")
        assert not compare.passed(compare.judge(numbers, cell.limits)), \
            numbers


def test_serving_control_fp8_is_not_correct(monkeypatch, tmp_path):
    """At 8 layers of width 256 and a vocabulary of 8192 (the smallest
    size tried at which float8 products move the logits as they do at full
    size), over 200 served tokens."""
    from bench.lib import program
    monkeypatch.setattr(program, "use_compile_cache", lambda: "off")
    cell = tiny_cell(serve_cell(tmp_path))
    cell.config = dict(cell.config, hidden_size=256, intermediate_size=512,
                       head_dim=64, num_hidden_layers=8, vocab_size=8192)
    cell.traffic = dict(cell.traffic, check_tokens=200)
    drv = cell.driver()
    for seed in (5, 6):
        ctx = _ctx(cell, seed)
        _, sample, _ = drv.measure(ctx)
        assert drv.served_gap(ctx, sample) <= cell.limits["served_logit_gap"]
        gap = drv.served_gap(ctx, sample, control="float8")
        assert gap > cell.limits["served_logit_gap"], gap

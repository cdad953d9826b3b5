"""Model families: the dense family gives the numbers its code gave before
it moved into ``bench/models/dense.py``, and a family of another
architecture joins the benchmark by new files alone."""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import runner, trace as trace_lib
from bench.lib.peaks import PEAKS
from bench.lib.seeds import jax_key
from bench.lib.spec import BENCH, ROOT, Cell, family

HERE = Path(__file__).resolve().parent
# Recorded from ``bench/reference/weights.py`` and ``decoder.py`` before
# their code moved into the dense family, by ``readings`` below.
RECORDED = json.loads((HERE / "dense_readings.json").read_text())


def _tiny_config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return family(cfg).tiny(cfg)


def _leaves(tree) -> dict:
    """path -> [shape, float64 sum, first three values] of each leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for p, x in flat:
        a = np.asarray(x, np.float32)
        out[jax.tree_util.keystr(p)] = [
            list(a.shape), float(np.sum(a, dtype=np.float64)),
            [float(v) for v in a.reshape(-1)[:3]]]
    return out


def readings(fam, cfg: dict, part: str) -> dict:
    canon = jax.jit(lambda k: fam.make(cfg, k))(jax_key(2718281828, 0))
    if part == "weights":
        return {"canonical": _leaves(canon)}
    if part == "layouts":
        return {"unrolled": _leaves(fam.pack_unrolled(canon)),
                "scanned": _leaves(fam.pack_scanned(canon))}
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 16)),
                         jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    out = {}
    for prec in ("float32", "bfloat16"):
        out[f"loss.{prec}"] = float(jax.jit(lambda c: fam.loss(
            cfg, c, tokens, labels, precision=prec))(canon))
    for prec in ("float32", "float8"):
        z = np.asarray(jax.jit(lambda c: fam.logits(
            cfg, c, tokens, precision=prec))(canon), np.float32)
        out[f"logits.{prec}"] = [float(np.sum(z, dtype=np.float64)),
                                 [float(v) for v in z.reshape(-1)[:3]]]
    return out


@pytest.mark.parametrize("part", ["weights", "layouts", "reference"])
@pytest.mark.parametrize("config", ["repro-100m", "qwen1.5-0.5b"])
def test_dense_family_gives_the_recorded_numbers(config, part):
    cfg = _tiny_config(config)
    fam = family(cfg)
    got = readings(fam, cfg, part)
    assert got == {k: RECORDED[config][k] for k in got}
    if part == "layouts":
        canon = jax.jit(lambda k: fam.make(cfg, k))(jax_key(1, 0))
        back = fam.unpack_unrolled(fam.pack_unrolled(canon))
        assert sorted(back) == sorted(canon)
        assert all(np.array_equal(back[k], canon[k]) for k in canon)


# The added family's cell: a configuration at test size, a train mix, the
# limits of the training cells, entries in BENCHMARK.json.
CELL = "train.tiny-untied.rq8ef"
STEPS, STEP_NS, CODEC_NS = 4, 200_000_000, 20_000_000
CODEC_OP = "custom-call.5"


def _hashes(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def _add_untied_family(root: Path) -> dict:
    """Add the family, its configuration and one training cell to the
    benchmark under ``root``; return the configuration."""
    b = root / "bench"
    shutil.copy(HERE / "untied_family.py", b / "models" / "dense_untied.py")
    cfg = dict(_tiny_config("repro-100m"), family="dense_untied",
               tie_word_embeddings=False, program_arch="tiny-untied")
    (b / "configs" / "tiny-untied.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "lm8x1024.rq8ef.json").read_text())
    mix.update(batch=8, seq_len=32, distinct_batches=4, trace_seconds=0.5)
    (b / "traffic" / "lm8x32.rq8ef.json").write_text(json.dumps(mix))
    shutil.copy(b / "limits" / "train.repro-100m.rq8ef.json",
                b / "limits" / f"{CELL}.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-untied", "file": "bench/configs/tiny-untied.json",
        "source": "tests/bench/untied_family.py", "reduced": [],
        "why": "a dense decoder with an untied head"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-untied", "traffic": "lm8x32.rq8ef",
        "chips": 1, "why": "the training loop on another family"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "mfu.train",
                         "codec_roofline.train"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cfg


def _device_trace(trace_dir, *, n_devices, hlo_text=""):
    """The CPU has no device plane: stand in a chip that ran ``STEPS``
    train steps, each with one codec operation, once the profiler has
    written its trace of the run."""
    assert list(Path(trace_dir).rglob("*.xplane.pb"))
    modules = [("jit_train_step(1)", k * STEP_NS, (k + 1) * STEP_NS)
               for k in range(STEPS)]
    ops = [(CODEC_OP, k * STEP_NS, k * STEP_NS + CODEC_NS)
           for k in range(STEPS)]
    window = (0, STEPS * STEP_NS)
    dev = trace_lib.DeviceTrace(ops, modules, window)
    return trace_lib.TraceData(
        [dev], [], window,
        {CODEC_OP: "jit(train_step)/jit(_qdq_flat_impl)/pallas_call"})


def test_a_family_is_added_by_new_files_alone(cpu_run, monkeypatch,
                                              tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path / "bench")
    cfg = _add_untied_family(tmp_path)
    readings_seen = []
    real_reading = runner.Reading

    def reading(*args):
        readings_seen.append(real_reading(*args))
        return readings_seen[-1]
    monkeypatch.setattr(runner, "Reading", reading)
    monkeypatch.setattr(trace_lib, "load", _device_trace)

    res = cpu_run(Cell(CELL, root=tmp_path), trace=True)

    assert res["correct"], res["checks"]
    fam = readings_seen[0].family
    assert fam.__name__ == "bench_family_dense_untied"
    peaks = PEAKS["TPU v5 lite"]
    m = res["metrics"]
    tokens = STEPS * 8 * 32
    assert m["mfu.train"]["value"] == pytest.approx(
        100 * fam.model_flops_per_token(cfg, 32) * tokens
        / (STEPS * STEP_NS * 1e-9) / peaks["bf16_flops_per_s"])
    # the head's d x vocab parameters cross the codec too
    dense = family(_tiny_config("repro-100m"))
    assert fam.n_params(cfg) == dense.n_params(cfg) + 64 * 256
    assert m["codec_roofline.train"]["value"] == pytest.approx(
        100 * 8 * fam.n_params(cfg) / peaks["hbm_bytes_per_s"]
        / (CODEC_NS * 1e-9))
    after = _hashes(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    assert before == {k: v for k, v in _hashes(BENCH).items()
                      if "__pycache__" not in k}

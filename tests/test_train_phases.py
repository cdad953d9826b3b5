"""The train step names its phases. Every operation of the compiled step
(reduced config, rq8 gradient codec with error feedback) lies under one
phase scope, under the codec's kernels or is a collective, so a profiler
trace of the step can be split by phase. Checked on one device and, in a
process of its own, on four virtual devices."""
import json
import os
import re
import subprocess
import sys

import pytest

PHASE_SCOPES = ("train.forward", "train.clip", "train.flatten",
                "train.error_feedback", "train.codec", "train.unflatten",
                "train.optimizer")
CODEC_SCOPE = "_qdq_flat_impl"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(')


def compiled_step_text(n_devices: int) -> str:
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.dist import sharding
    from repro.launch import mesh as mesh_lib
    from repro.optim import make_optimizer
    from repro.train import steps

    cfg = configs.get_config("repro-100m").reduced(n_layers=2, d_model=64,
                                                   vocab=256)
    opt = make_optimizer("adamw", 1e-3)
    scfg = steps.TrainStepConfig(grad_compression="rq8",
                                 error_feedback=True)
    state = steps.abstract_train_state(cfg, opt, step_cfg=scfg)
    batch = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32)
             for k in ("tokens", "labels")}
    mesh = mesh_lib.make_mesh((n_devices, 1), ("data", "model"),
                              devices=jax.devices()[:n_devices])
    with jax.set_mesh(mesh):
        rep = sharding.replicated(mesh)
        step = jax.jit(steps.make_train_step(cfg, opt, scfg),
                       in_shardings=(rep, sharding.batch_shardings(batch,
                                                                   mesh)),
                       out_shardings=(rep, rep))
        return step.lower(state, batch).compile().as_text()


def unplaced(hlo_text: str) -> list:
    """Entry-computation operations with a scope path that is under no
    phase, not under the codec and not a collective."""
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    out, placed = [], 0
    for line in entry.splitlines()[1:]:
        m = _INSTR.match(line)
        scope = re.search(r'op_name="([^"]*)"', line)
        if not m or not scope or m.group(2) == "parameter":
            continue
        path = scope.group(1)
        if (m.group(2).startswith(COLLECTIVES) or CODEC_SCOPE in path
                or any(re.search(rf"(^|/|\(){re.escape(s)}(\)|/|$)", path)
                       for s in PHASE_SCOPES)):
            placed += 1
        else:
            out.append(f"{m.group(1)} {m.group(2)} {path}")
    assert placed > 0, "no operation of the step carries a scope"
    return out


@pytest.fixture(scope="module")
def one_device_text():
    return compiled_step_text(1)


def test_every_op_of_the_step_is_under_a_phase_on_one_device(
        one_device_text):
    assert unplaced(one_device_text) == []


def test_every_op_of_the_step_is_under_a_phase_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["collectives"] > 0
    assert res["unplaced"] == []


def test_forward_and_backward_carry_the_forward_scope(one_device_text):
    paths = re.findall(r'op_name="([^"]*)"', one_device_text)
    assert any("jvp(train.forward)" in p and "transpose(" not in p
               for p in paths)
    assert any("transpose(jvp(train.forward))" in p for p in paths)


@pytest.mark.parametrize("scope", PHASE_SCOPES[1:])
def test_each_phase_scope_reaches_the_compiled_step(one_device_text,
                                                   scope):
    assert f"/{scope}/" in one_device_text


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    import jax
    text = compiled_step_text(4)
    print(json.dumps({
        "devices": len(jax.devices()),
        "collectives": len(re.findall(
            rf"\s(?:{'|'.join(COLLECTIVES)})(?:-start)?\(", text)),
        "unplaced": unplaced(text)}))

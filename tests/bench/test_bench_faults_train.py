"""The training cells' runs, driven on the CPU at a tiny size with the
timed path sound and then broken underneath: a sound run is correct, and
each fault a training cell can have makes ``correct`` false. The
four-chip cell runs on four virtual CPU devices in a process of its own."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, tiny_cell

CELLS = ["train.repro-100m.rq8ef", "train.repro-100m.dp4.rq8ef"]


def _break_step(monkeypatch, fault: str):
    from repro.train import steps
    real = steps.make_train_step

    def broken(mc, opt, scfg):
        step = real(mc, opt, scfg)

        def run(state, batch):
            if fault == "state_unchanged":
                return state, step(state, batch)[1]
            b = batch["tokens"].shape[0]
            return step(state, {k: v[:b // 2] for k, v in batch.items()})
        return run
    monkeypatch.setattr(steps, "make_train_step", broken)


def test_sound_run_is_correct(cpu_run):
    res = cpu_run(tiny_cell(CELLS[0]))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_makes_the_run_incorrect(cpu_run, monkeypatch, fault):
    c = tiny_cell(CELLS[0])
    _break_step(monkeypatch, fault)
    res = cpu_run(c)
    assert not res["correct"], res["checks"]


def _four_devices(mode: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "dp4_cpu_run.py", mode],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_four_chip_cell_with_and_without_the_exchange(mode, correct):
    res = _four_devices(mode)
    assert res["count"] == 4
    assert res["correct"] is correct, res["checks"]

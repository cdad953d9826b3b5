"""BENCHMARK.json against the contract it is written to, and the harness
finding each file by name."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from bench.lib.spec import BENCH, ROOT, Cell, SpecError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"] \
                or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_resolves_and_reports_what_it_must(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    four = 0
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        assert cell.chips in (1, 4)
        four += cell.chips == 4
        assert cell.driver().run
        readers = cell.metric_readers()
        assert readers and all(callable(r.read) for r in readers.values())
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported & e2e) >= 2
        assert all(m["moves"] in reported for m in cell.per_layer)
        assert len(w["why"]) <= 200
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_a_new_traffic_file_is_found_by_name_alone(tmp_path, bench):
    """A later cell adds a traffic file and a BENCHMARK.json entry; no
    file of the harness changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.loads((BENCH / "traffic" / "lm8x1024.rq8ef.json").read_text())
    mix["codec"] = dict(mix["codec"], name="none", error_feedback=False)
    (tmp_path / "bench" / "traffic" / "lm8x1024.none.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "limits" / "train.new.json").write_text(
        (BENCH / "limits" / "train.repro-100m.rq8ef.json").read_text())
    spec = dict(bench)
    spec["workloads"] = bench["workloads"] + [
        {"name": "train.new", "config": "repro-100m",
         "traffic": "lm8x1024.none", "chips": 1, "why": "no codec"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = Cell("train.new", root=tmp_path)
    assert cell.traffic["codec"]["name"] == "none"
    assert cell.config["hidden_size"] == 768
    assert cell.driver().first_steps


@pytest.mark.parametrize("family", [None, "no-such-family"])
def test_a_configuration_names_a_family_that_exists(tmp_path, family):
    """A configuration without ``"family"``, or naming a family with no
    module, is refused; there is no default."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "bench" / "configs" / "repro-100m.json"
    cfg = json.loads(path.read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    cell = Cell("train.repro-100m.rq8ef", root=tmp_path)
    with pytest.raises(SpecError):
        cell.family()


def _run_bench(cwd, *extra, env_platform="cpu"):
    import os
    env = dict(os.environ, JAX_PLATFORMS=env_platform)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.repro-100m.rq8ef", "--seed", "1", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result_and_a_nonzero_exit():
    out = _run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""

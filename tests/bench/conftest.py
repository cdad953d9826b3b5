"""Shared set-up of the chip benchmark's CPU tests: the repository root on
``sys.path`` (the harness is the ``bench`` package there) and tiny cells."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

# The serving driver's parameters at a size a test run holds. No serving
# cell is in BENCHMARK.json; the tests add one as a later cell would be
# added, by a traffic file, a limits file and entries.
SERVE_MIX = {
    "driver": "serve", "generator": "open_loop",
    "about": "open-loop Poisson requests at test size",
    "slots": 4, "max_len": 96, "max_queue": 4096, "rate_per_s": 4.0,
    "pool_seed": 20260, "prompt_buckets": [8, 16, 32], "prompt_median": 12,
    "prompt_sigma": 0.8, "output_median": 8, "output_sigma": 0.7,
    "output_min": 2, "output_max": 40, "drain_cap_s": 60,
    "ttft_quantile": 50, "itl_quantile": 95, "check_tokens": 60,
    "trace_seconds": 0.5}
SERVE_LIMITS = {"served_logit_gap": 0.1, "unanswered": 0, "wrong_length": 0}
SERVE_CELL = "serve.qwen1.5-0.5b.test"


def serve_cell(root):
    """A serving cell of the qwen1.5-0.5b configuration, added to a copy of
    the benchmark under ``root`` by data alone."""
    from bench.lib.spec import BENCH, Cell
    from bench.lib.spec import ROOT as REPO
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "traffic" / "chat-test.json").write_text(
        json.dumps(SERVE_MIX))
    (root / "bench" / "limits" / f"{SERVE_CELL}.json").write_text(
        json.dumps(SERVE_LIMITS))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "qwen1.5-0.5b", "file": "bench/configs/qwen1.5-0.5b.json",
        "source": "https://huggingface.co/Qwen/Qwen1.5-0.5B/blob/main/"
                  "config.json", "reduced": [], "why": "a served decoder"})
    spec["workloads"].append({
        "name": SERVE_CELL, "config": "qwen1.5-0.5b", "traffic": "chat-test",
        "chips": 1, "why": "the serving driver at test size"})
    spec["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": [SERVE_CELL]}
        for name in ("ttft_p50_ms", "itl_p95_ms")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Cell(SERVE_CELL, root=root)


def tiny_cell(cell):
    """A cell (or the cell of BENCHMARK.json named ``cell``) at a size a
    test run holds: the configuration cut by its family's ``tiny``, the
    mix's shape kept."""
    from bench.lib.spec import Cell
    if isinstance(cell, str):
        cell = Cell(cell)
    cell.config = cell.family().tiny(cell.config)
    if cell.traffic["driver"] == "train":
        cell.traffic = dict(cell.traffic, batch=8, seq_len=32,
                            distinct_batches=4, trace_seconds=0.5)
    return cell


@pytest.fixture
def cpu_run(monkeypatch):
    """Run a tiny cell's driver on the CPU: the harness's look for a chip
    and its peaks table are skipped, the compile cache left alone."""
    import jax
    from bench.lib import program, runner
    from bench.lib.peaks import PEAKS
    monkeypatch.setattr(runner, "peaks_for", lambda kind: PEAKS["TPU v5 lite"])
    monkeypatch.setattr(program, "use_compile_cache", lambda: "off")

    def go(cell, *, seed=12345, seconds=1.5, trace=False):
        return runner.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                               devices=jax.devices()[:1], t_process=0.0)
    return go

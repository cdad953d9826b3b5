"""Chip benchmark of this repository: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, correctness limits and per-layer
metrics are found by name from ``BENCHMARK.json`` (see ``lib/spec.py``).
The run checks that JAX sees as many accelerator chips as the cell asks
for (else it exits 2 and prints no result), sets up the cell, measures for
``--seconds`` and checks what the timed path produced against a plain
reference. With ``--trace 0`` the result line holds the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of part of the window gives
its per-layer metrics and a breakdown. The last stdout line is the result,
one JSON object; the numbers compared with their limits are the last lines
of stderr and the last key of the result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.lib import runner  # noqa: E402
from bench.lib.spec import Cell, SpecError  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
    except SpecError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    devices = runner.accelerators(cell.chips)
    if devices is None:
        return 2
    result = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), devices=devices,
                             t_process=T_PROCESS)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

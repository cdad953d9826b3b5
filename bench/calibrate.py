"""Readings that the correctness limits and the serving rate are set from.

Not run by the benchmark's own runs. On the chip, from the checkout root:

    python3 bench/calibrate.py train --workload <train cell> \
        --seeds 1,2,... --control-seeds 1,2,3 --fault-seeds 1,2,3
    python3 bench/calibrate.py look --workload <train cell> --seeds 1,2,3
    python3 bench/calibrate.py serve --workload <serve cell> --seconds 20 \
        --seeds 1,2,... --control-seeds 1,2,3
    python3 bench/calibrate.py sweep --workload <serve cell> --seconds 30 \
        --rates 1.0,1.5,2.0

``train``: per seed, the program's first steps against the float32
reference (the lower readings); the reference in bfloat16 put in the
program's place (the control); the reference with half of the batch left
out, and on several chips with one chip's rows alone (planted faults).
``look``: per seed, what moves ``change_gap`` (see ``look``).
``serve``: per seed, a short window at the cell's load, then the widest
logit gap of the served tokens (lower readings) and of the tokens that the
reference in float8 puts first (the control).
``sweep``: the serving loop at each rate, for the knee.
Each reading is one JSON line on stdout.
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

from bench.lib import program, runner, stats  # noqa: E402
from bench.lib.spec import Cell  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def _worst(prog: dict, ref: dict, k: int = 3) -> list:
    """The ``k`` leaves of the widest gap: [leaf, gap, program, ref]."""
    import statistics
    med = statistics.median(ref.values())
    rows = [[name, abs(prog[name] - r) / max(r, med), prog[name], r]
            for name, r in ref.items()]
    return sorted(rows, key=lambda x: -x[1])[:k]


def train(cell, devices, args) -> None:
    import gc
    import jax
    drv = cell.driver()
    for seed in _ints(args.seeds):
        ctx = runner.Ctx(cell, seed=seed, seconds=0, trace=False,
                         devices=devices, t_process=T_PROCESS)
        t = time.perf_counter()
        loop = drv.Loop(ctx)
        with jax.set_mesh(loop.mesh):
            losses, grad1, change = drv.first_steps(loop)
        batches = drv.check_batches(loop)
        del loop
        gc.collect()
        ref = drv.reference(ctx, batches)
        emit(seed=seed, kind="program", losses=losses,
             ref_losses=ref["losses"],
             **drv.compare_with(ref, losses, grad1, change),
             seconds=time.perf_counter() - t,
             worst_change_leaves=_worst(change, ref["change"]),
             worst_grad1_leaves=_worst(grad1, ref["grad1"]))
        kinds = []
        if seed in _ints(args.control_seeds):
            kinds.append(("control_bf16", {"precision": "bfloat16"}))
        if seed in _ints(args.fault_seeds):
            b = cell.traffic["batch"]
            kinds.append(("fault_half_batch", {"rows": slice(0, b // 2)}))
            if cell.chips > 1:
                kinds.append(("fault_no_exchange",
                              {"rows": slice(0, b // cell.chips)}))
        for kind, kw in kinds:
            got = drv.reference(ctx, batches, **kw)
            emit(seed=seed, kind=kind, losses=got["losses"],
                 **drv.compare_with(ref, got["losses"], got["grad1"],
                                    got["change"]))


def _grid(x, cap: int, levels: int):
    """Each bucket's minimum and step, and each element's level, of a
    dequantized flat message."""
    import numpy as np
    starts = np.arange(0, x.shape[0], cap)
    counts = np.diff(np.append(starts, x.shape[0]))
    lo = np.minimum.reduceat(x, starts)
    hi = np.maximum.reduceat(x, starts)
    step = np.where(hi > lo, (hi - lo) / levels, 1.0).astype(np.float32)
    lev = np.rint((x - np.repeat(lo, counts)) / np.repeat(step, counts))
    return lo, step, lev.astype(np.int16)


def _message_pair(xa, ga, xb, gb, leaves: list, names: list) -> dict:
    """How far side a's message grid lies from side b's: the shift of each
    bucket's minimum in b's levels, the change of its step, and the shares
    of elements that land on another level or another sign, overall and
    in the leaves ``names``."""
    import numpy as np
    (lo_a, st_a, lv_a), (lo_b, st_b, lv_b) = ga, gb
    shift = np.abs(lo_a - lo_b) / st_b
    flips = np.sign(xa) != np.sign(xb)
    offsets = dict(zip([n for n, _ in leaves],
                       np.cumsum([0] + [s for _, s in leaves])[:-1]))
    sizes = dict(leaves)
    return {"lo_shift_levels_max": float(shift.max()),
            "lo_shift_levels_median": float(np.median(shift)),
            "step_rel_max": float(np.max(np.abs(st_a / st_b - 1))),
            "level_differs": float(np.mean(lv_a != lv_b)),
            "sign_differs": float(np.mean(flips)),
            "sign_differs_in": {n: float(np.mean(
                flips[offsets[n]: offsets[n] + sizes[n]])) for n in names}}


def _gaps(drv, ref: dict, got: dict) -> dict:
    """The compared numbers of ``got`` against ``ref``, and the median
    leaf's change gap beside the worst leaf's."""
    import statistics
    out = drv.compare_with(ref, got["losses"], got["grad1"], got["change"])
    out.pop("n_leaves_left_out")
    med = statistics.median(ref["change"].values())
    out["change_gap_median_leaf"] = statistics.median(
        abs(got["change"][k] - r) / max(r, med)
        for k, r in ref["change"].items())
    return out


def look(cell, devices, args) -> None:
    """Why ``change_gap`` reads a few seeds twice as high as others. Per
    seed: the program's first steps against the reference at HIGHEST (the
    yardstick) and at DEFAULT matrix precision (the program's own setting);
    the two references against each other; and the first gradient message
    of each side read back as its grid (each bucket's minimum and step, each
    element's level and sign). The first seed also gives the compiled step's
    memory analysis beside the runtime's memory counters."""
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    drv = cell.driver()
    mix = cell.traffic
    b1, codec = mix["optimizer"]["b1"], mix["codec"]
    cap, levels = codec["bucket_elems"], (1 << codec["bits"]) - 1
    flat = jax.jit(lambda t: jnp.concatenate(
        [x.reshape(-1).astype(jnp.float32) for x in jax.tree.leaves(t)]))
    for i, seed in enumerate(_ints(args.seeds)):
        ctx = runner.Ctx(cell, seed=seed, seconds=0, trace=False,
                         devices=devices, t_process=T_PROCESS)
        t0 = time.perf_counter()
        loop = drv.Loop(ctx)
        first = {}

        def keep(lp):
            m = lp.state["opt"]["m"]
            first["msg"] = np.asarray(flat(m)) / np.float32(1 - b1)
            first["sizes"] = [x.size for x in jax.tree.leaves(m)]

        with jax.set_mesh(loop.mesh):
            losses, grad1, change = drv.first_steps(loop, on_first=keep)
        prog_msg = first["msg"]
        leaves = list(zip(grad1, first["sizes"]))
        if i == 0:
            ma = loop.compiled.memory_analysis()
            emit(kind="memory", compiled={
                a: int(getattr(ma, a)) for a in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "alias_size_in_bytes", "temp_size_in_bytes",
                    "generated_code_size_in_bytes")},
                runtime={str(d): d.memory_stats() for d in devices})
        batches = drv.check_batches(loop)
        del loop
        gc.collect()
        hi = drv.reference(ctx, batches, keep_message=True)
        df = drv.reference(ctx, batches, precision="float32_default",
                           keep_message=True)
        prog = {"losses": losses, "grad1": grad1, "change": change}
        worst = [w[0] for w in _worst(change, hi["change"])]
        grids = {k: _grid(x, cap, levels) for k, x in (
            ("prog", prog_msg), ("highest", hi["message1"]),
            ("default", df["message1"]))}
        msgs = {"prog": prog_msg, "highest": hi["message1"],
                "default": df["message1"]}
        pairs = (("prog", "highest"), ("prog", "default"),
                 ("default", "highest"))
        sides = {"prog": prog, "highest": hi, "default": df}
        emit(seed=seed, kind="look", losses=losses,
             numbers={f"{a}_vs_{b}": _gaps(drv, sides[b], sides[a])
                      for a, b in pairs},
             message1={f"{a}_vs_{b}": _message_pair(
                 msgs[a], grids[a], msgs[b], grids[b], leaves, worst)
                 for a, b in pairs},
             worst_change_leaves=_worst(change, hi["change"]),
             worst_change_leaves_default=_worst(df["change"], hi["change"]),
             seconds=time.perf_counter() - t0)


def serve(cell, devices, args) -> None:
    drv = cell.driver()
    for seed in _ints(args.seeds):
        ctx = runner.Ctx(cell, seed=seed, seconds=args.seconds, trace=False,
                         devices=devices, t_process=time.perf_counter())
        out, sample, misses = drv.measure(ctx)
        row = dict(seed=seed, kind="program", **misses,
                   attempted=out["attempted"], failed=out["failed"],
                   sampled_tokens=sum(len(t) for _, t in sample),
                   served_logit_gap=drv.served_gap(ctx, sample))
        if seed in _ints(args.control_seeds):
            row["control_fp8_gap"] = drv.served_gap(ctx, sample,
                                                    control="float8")
            row["control_bf16_gap"] = drv.served_gap(ctx, sample,
                                                     control="bfloat16")
        emit(**row)


def sweep(cell, devices, args) -> None:
    drv = cell.driver()
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_per_s=rate,
                            drain_cap_s=args.drain_cap)
        ctx = runner.Ctx(cell, seed=args.seed, seconds=args.seconds,
                         trace=False, devices=devices,
                         t_process=time.perf_counter())
        out, _, misses = drv.measure(ctx)
        f = out["facts"]
        done = [x for x in f["ttft_ms"] if x != float("inf")]
        emit(rate=rate, attempted=out["attempted"], failed=out["failed"],
             **misses, backlog_at_close=f["backlog_at_close"],
             ttft_p50_ms=stats.nearest_rank(f["ttft_ms"], 50),
             ttft_p90_ms=stats.nearest_rank(f["ttft_ms"], 90),
             ttft_max_ms=max(f["ttft_ms"]) if done else None,
             itl_p50_ms=stats.nearest_rank(f["gaps_ms"], 50),
             itl_p90_ms=stats.nearest_rank(f["gaps_ms"], 90),
             itl_p95_ms=stats.nearest_rank(f["gaps_ms"], 95),
             itl_p99_ms=stats.nearest_rank(f["gaps_ms"], 99),
             n_gaps=len(f["gaps_ms"]), setup_s=out["setup_s"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("train", "look", "serve", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain-cap", type=float, default=20.0)
    args = ap.parse_args()
    cell = Cell(args.workload)
    devices = runner.accelerators(cell.chips)
    if devices is None:
        return 2
    program.use_compile_cache()
    {"train": train, "look": look, "serve": serve, "sweep": sweep}[args.mode](
        cell, devices, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^^ MUST run before any other import: jax locks the device count on first
# backend initialization. Everything below is ordinary.

"""Multi-pod dry-run: prove the distribution config is coherent.
(No `from __future__ import annotations` here: the XLA_FLAGS lines above
must stay the first statements in the module.)

For every (architecture x input shape) combination this lowers + compiles
the real step function (train_step / prefill_step / serve_step) against the
production mesh with ShapeDtypeStruct inputs — no arrays are allocated —
and extracts:

  * compiled.memory_analysis()   -> bytes/device (proves HBM fit)
  * compiled.cost_analysis()     -> HLO FLOPs + bytes accessed
  * collective bytes             -> parsed from the compiled HLO (all-gather
                                    / all-reduce / reduce-scatter /
                                    all-to-all / collective-permute)

Outputs a JSON record per combo consumed by benchmarks/roofline.py and
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch command-r-35b \
      --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.pipeline import make_batch_shapes
from repro.dist import sharding
from repro.launch import mesh as mesh_lib
from repro.models import transformer_scan
from repro.models.common import INPUT_SHAPES, InputShape, ModelConfig
from repro.optim import make_optimizer
from repro.train import steps

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# --------------------------------------------------------------------------
# input specs
# --------------------------------------------------------------------------


def _serve_window(cfg: ModelConfig, shape: InputShape) -> int:
    """Sliding window used for attn-block KV caches at this shape.

    long_500k REQUIRES sub-quadratic state: dense/moe/vlm/audio archs use
    their sliding_window_decode; ssm/hybrid archs have O(1)/O(window) state
    anyway (their 'window' only applies to local_attn blocks, which always
    use cfg.local_window).
    """
    if shape.name == "long_500k":
        return cfg.sliding_window_decode
    return 0


def input_specs(arch: str, shape_name: str, *,
                optimizer: str = "adamw", moment_dtype=None,
                step_cfg: Optional[steps.TrainStepConfig] = None
                ) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of the step function."""
    cfg = configs.get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    out: dict[str, Any] = {"cfg": cfg, "shape": shape}
    batch = make_batch_shapes(cfg, shape, dtype=jnp.bfloat16)
    out["batch"] = batch
    if shape.kind == "train":
        scfg = step_cfg or default_train_cfg(cfg)
        opt = make_optimizer(optimizer, 3e-4,
                             moment_dtype=moment_dtype
                             or default_moment_dtype(cfg)) \
            if optimizer != "sgd" else make_optimizer("sgd", 3e-4)
        out["state"] = steps.abstract_train_state(cfg, opt, step_cfg=scfg)
        out["step_cfg"] = scfg
        out["optimizer"] = opt
    elif shape.kind == "decode":
        params = jax.eval_shape(
            lambda k: transformer_scan.init(cfg, k, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        out["params"] = params
        window = _serve_window(cfg, shape)
        mem = None
        if cfg.is_encdec:
            mem = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len, cfg.d_model),
                jnp.bfloat16)
        out["decode_state"] = jax.eval_shape(
            lambda p, m: transformer_scan.init_decode_state(
                p, cfg, shape.global_batch, shape.seq_len, window=window,
                dtype=jnp.bfloat16, memory=m),
            params, mem)
    else:  # prefill
        params = jax.eval_shape(
            lambda k: transformer_scan.init(cfg, k, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        out["params"] = params
    return out


def default_train_cfg(cfg: ModelConfig) -> steps.TrainStepConfig:
    return steps.TrainStepConfig(remat=True, grad_clip=1.0,
                                 param_dtype=jnp.bfloat16, scan_layers=True)


def default_moment_dtype(cfg: ModelConfig):
    # grok's 314B needs bf16 Adam moments to fit 16GB/chip (EXPERIMENTS §Dry-run)
    big = cfg.param_count() > 80e9
    return jnp.bfloat16 if big else jnp.float32


# --------------------------------------------------------------------------
# lowering one combo
# --------------------------------------------------------------------------


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                optimizer: str = "adamw",
                step_cfg: Optional[steps.TrainStepConfig] = None):
    """Returns (lowered, specs) for the given combination."""
    spec = input_specs(arch, shape_name, optimizer=optimizer,
                       step_cfg=step_cfg)
    cfg, shape = spec["cfg"], spec["shape"]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    sharding.set_activation_batch_axes(
        ("pod", "data") if multi_pod else ("data",))

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn = steps.make_train_step(cfg, spec["optimizer"],
                                       spec["step_cfg"])
            state_sh = _state_shardings(spec["state"], mesh)
            batch_sh = sharding.batch_shardings(spec["batch"], mesh)
            jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(spec["state"], spec["batch"])
        elif shape.kind == "decode":
            fn = steps.make_serve_step(cfg, scan_layers=True)
            p_sh = sharding.params_shardings(spec["params"], mesh)
            c_sh = sharding.cache_shardings(spec["decode_state"], mesh)
            b_sh = sharding.batch_shardings(spec["batch"], mesh)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(spec["params"], spec["decode_state"],
                                   spec["batch"])
        else:  # prefill
            fn = steps.make_prefill_step(cfg, scan_layers=True,
                                         logits_positions="last")
            p_sh = sharding.params_shardings(spec["params"], mesh)
            b_sh = sharding.batch_shardings(spec["batch"], mesh)
            jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(spec["params"], spec["batch"])
    return lowered, spec, mesh


def _state_shardings(state_shapes, mesh):
    """Train-state sharding: params/moments by param rules; the flat
    ec_err residual buffer FSDP-shards over the data axes; scalars and
    rng replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    def rule(path, leaf):
        names = sharding._path_names(path)
        if names and names[0] == "ec_err":
            # single flat fp32 buffer (fused codec tier): 1-D shard over
            # the full data-axis tuple when divisible, else replicate
            spec = PartitionSpec(sharding._maybe(
                sharding._ACT_BATCH_AXES, leaf.shape[0], mesh))
            return NamedSharding(mesh, spec)
        if names and names[0] == "params":
            return sharding.params_shardings_leaf(path[1:], leaf, mesh)
        if names and names[0] == "opt" and len(names) > 1 \
                and names[1] in ("m", "v"):
            return sharding.params_shardings_leaf(path[2:], leaf, mesh)
        return sharding.replicated(mesh)

    return jax.tree_util.tree_map_with_path(rule, state_shapes)


# --------------------------------------------------------------------------
# HLO analysis
# --------------------------------------------------------------------------


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-operand bytes of every collective op in the HLO."""
    totals = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    # e.g. "%all-reduce.1 = bf16[512,128]{1,0} all-reduce(...)"
    #      "... = (f32[128]{0}, f32[64]{0}) all-gather(...)"
    array_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
                      + r")(-start|-done)?\(", line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue  # counted at -start
        result, op = m.group(1), m.group(2)
        nbytes = 0.0
        for dm in array_re.finditer(result):
            dt, dims = dm.group(1), dm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[op] += nbytes
        counts[op] += 1
    totals["total"] = sum(totals[k] for k in _COLLECTIVES)
    totals["counts"] = counts
    return totals


def analyze(compiled, lowered=None) -> dict[str, Any]:
    from repro.launch import hlo_analysis
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    rec: dict[str, Any] = {
        # raw cost_analysis (counts while bodies ONCE - kept for reference)
        "flops_body_once": float(cost.get("flops", 0.0)),
        "bytes_accessed_body_once": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        rec[attr] = int(getattr(mem, attr, 0))
    # trip-count-aware per-device totals (launch/hlo_analysis.py)
    costs = hlo_analysis.analyze_hlo(compiled.as_text())
    rec["dot_flops"] = costs.dot_flops
    rec["collectives"] = costs.as_dict()
    rec["collectives"]["total"] = costs.collective_bytes
    return rec


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            optimizer: str = "adamw",
            step_cfg: Optional[steps.TrainStepConfig] = None,
            verbose: bool = True) -> dict[str, Any]:
    t0 = time.time()
    lowered, spec, mesh = lower_combo(arch, shape_name, multi_pod=multi_pod,
                                      optimizer=optimizer, step_cfg=step_cfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    rec = analyze(compiled)
    rec.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params": configs.get_config(arch).param_count(),
        "active_params": configs.get_config(arch).active_param_count(),
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={rec['mesh']} "
              f"dot_flops={rec['dot_flops']:.3e} "
              f"coll={rec['collectives']['total']:.3e}B "
              f"temp={rec['temp_size_in_bytes']/2**30:.2f}GiB "
              f"args={rec['argument_size_in_bytes']/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        sys.stdout.flush()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x all shapes")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    combos = []
    archs = list(configs.ASSIGNED) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    records = []
    failures = []
    for a, s, mp in combos:
        try:
            rec = run_one(a, s, multi_pod=mp)
            records.append(rec)
        except Exception as e:  # noqa: BLE001 — report, keep going
            failures.append((a, s, mp, repr(e)))
            print(f"[dryrun] FAIL {a} x {s} multi_pod={mp}: {e!r}")
    if args.out:
        with open(args.out, "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
    print(f"[dryrun] {len(records)} OK, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("  FAIL:", f_)
        sys.exit(1)


if __name__ == "__main__":
    main()

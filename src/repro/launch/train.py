"""Training launcher.

Runs real training on the host devices (reduced or small archs on CPU;
the same code drives a TPU slice when one is attached) with the paper's
communication relaxations selectable from the CLI:

  PYTHONPATH=src python -m repro.launch.train --arch repro-100m \
      --steps 200 --batch 8 --seq 256 \
      [--compression rq8] [--error-feedback] [--reduced] \
      [--ckpt-dir /tmp/ckpt] [--scan-layers]

On a multi-device host, data parallelism uses a ('data','model') mesh over
the available devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint import latest_checkpoint, load_state, save_state
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.optim import cosine_schedule, make_optimizer
from repro.train import steps


@dataclasses.dataclass
class TrainRun:
    """What ``main`` ran: the final state, the logged ``(step, loss)``
    pairs, the compiled step, and the last batch it was fed."""

    state: dict
    losses: list
    compiled_step: Any
    batch: dict


def main(argv=None, *, devices=None) -> TrainRun:
    """Train from CLI flags; ``devices`` (default: all of
    ``jax.devices()``) are the data-parallel mesh."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "momentum", "sgd"])
    ap.add_argument("--compression", default="none")
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-scale variant of the arch")
    ap.add_argument("--scan-layers", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.use_compile_cache()
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    devices = list(jax.devices() if devices is None else devices)
    n_dev = len(devices)
    mesh = mesh_lib.make_mesh((n_dev, 1), ("data", "model"), devices=devices)
    sharding.set_activation_batch_axes(("data",))
    print(f"[train] arch={cfg.arch_id} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={n_dev} batch={args.batch} seq={args.seq}")

    lr = cosine_schedule(args.lr, warmup=min(50, args.steps // 10 + 1),
                         total=args.steps)
    opt = make_optimizer(args.optimizer, lr)
    scfg = steps.TrainStepConfig(
        remat=args.remat, grad_compression=args.compression,
        error_feedback=args.error_feedback, scan_layers=args.scan_layers)
    state = steps.init_train_state(cfg, opt, jax.random.PRNGKey(args.seed),
                                   step_cfg=scfg)
    start = 0
    if args.ckpt_dir:
        ck = latest_checkpoint(args.ckpt_dir)
        if ck:
            state = load_state(jax.eval_shape(lambda: state), ck)
            start = int(state["step"])
            print(f"[train] resumed from {ck} at step {start}")

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq + 1,
                       batch=args.batch, seed=args.seed)
    losses = []
    compiled = batch = None
    with jax.set_mesh(mesh):
        # data parallel: state replicated on every device, batch split
        # over 'data'; the step keeps the state replicated
        rep = sharding.replicated(mesh)
        state = jax.device_put(state, rep)
        train_step = jax.jit(steps.make_train_step(cfg, opt, scfg),
                             out_shardings=(rep, rep), donate_argnums=(0,))
        t0 = time.time()
        for t in range(start, args.steps):
            batch = data.batch_at(t)
            batch = jax.device_put(
                batch, sharding.batch_shardings(batch, mesh))
            if compiled is None:
                tc = time.time()
                compiled = train_step.lower(state, batch).compile()
                print(f"[train] step compiled in {time.time() - tc:.1f}s")
                t0 = time.time()
            state, metrics = compiled(state, batch)
            if t % args.log_every == 0 or t == args.steps - 1:
                loss = float(metrics["loss"])
                losses.append((t, loss))
                dt = time.time() - t0
                tput = args.batch * args.seq * (t - start + 1) / max(dt, 1e-9)
                print(f"[train] step {t:5d} loss {loss:7.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"tok/s {tput:9.0f}")
            if args.ckpt_dir and (t + 1) % args.ckpt_every == 0:
                save_state(state, args.ckpt_dir, step=t + 1)
    if args.ckpt_dir:
        save_state(state, args.ckpt_dir, step=args.steps)
    print("[train] done")
    return TrainRun(state, losses, compiled, batch)


if __name__ == "__main__":
    main()

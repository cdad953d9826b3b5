"""Train loop: the program's jitted train step, driven as
``repro.launch.train.main`` drives it, on batches put one per step.

Set-up builds the train state once (weights made on the device from the
seed), compiles the step, and drives that same state through its first
``check_steps`` steps with the window's own call and feed, recording what
the correctness check compares. The window then continues the same state
for ``--seconds``; tokens per second are taken over every step completed
in it, ending in a wait for the last one. After the window the program's
state is freed and the plain reference follows the first steps.
"""
from __future__ import annotations

import collections
import math
import time

from bench.generators import lm_batches
from bench.lib import compare
from bench.lib import program  # noqa: F401  (puts the program on sys.path)
from bench.lib.seeds import jax_key
from bench.reference import train as ref_train

WEIGHTS_TAG, RNG_TAG = 0, 1


def _optimizer(mix):
    from repro.optim import cosine_schedule, make_optimizer
    o = mix["optimizer"]
    lr = cosine_schedule(o["lr"], warmup=o["warmup"], total=o["total"],
                         floor=o["floor"])
    return make_optimizer("adamw", lr, b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])


class Loop:
    """The compiled step, its state and its feed: one object from set-up
    through the window."""

    def __init__(self, ctx):
        import jax
        from repro.dist import sharding
        from repro.launch import mesh as mesh_lib
        from repro.train import steps

        self.ctx, cfg, mix, fam = ctx, ctx.config, ctx.mix, ctx.family
        self.mc = fam.program_config(cfg)
        self.mesh = mesh_lib.make_mesh(tuple(mix["mesh"]), ("data", "model"),
                                       devices=ctx.devices)
        sharding.set_activation_batch_axes(("data",))
        self.opt = _optimizer(mix)
        codec = mix["codec"]
        scfg = steps.TrainStepConfig(grad_clip=mix["grad_clip"],
                                     grad_compression=codec["name"],
                                     error_feedback=codec["error_feedback"])
        self.k_weights = jax_key(ctx.seed, WEIGHTS_TAG)
        self.k_rng = jax_key(ctx.seed, RNG_TAG)
        ctx.mark("imports and devices")
        self.batches = lm_batches.make(mix, self.mc.vocab, ctx.seed)
        ctx.mark("batches")
        with jax.set_mesh(self.mesh):
            rep = sharding.replicated(self.mesh)

            def build(kw, kr):
                st = steps.init_train_state(self.mc, self.opt, kr,
                                            step_cfg=scfg)
                ours = fam.pack_unrolled(fam.make(cfg, kw))
                if (jax.tree.structure(ours)
                        != jax.tree.structure(st["params"])):
                    raise ValueError("the program's parameter layout is not "
                                     "the one the benchmark packs")
                st["params"] = ours
                return st

            self.state = jax.jit(build, out_shardings=rep)(self.k_weights,
                                                           self.k_rng)
            jax.block_until_ready(self.state)
            ctx.mark("weights and state")
            self.shardings = sharding.batch_shardings(self.batches[0],
                                                      self.mesh)
            step = jax.jit(steps.make_train_step(self.mc, self.opt, scfg),
                           out_shardings=(rep, rep), donate_argnums=(0,))
            self.compiled = step.lower(self.state,
                                       self.put(0)).compile()
        ctx.mark("train step compile or cache load")
        self.n = 0

    def put(self, i: int):
        import jax
        return jax.device_put(self.batches[i % len(self.batches)],
                              self.shardings)

    def step(self):
        """One step on the next batch; returns the step's metrics."""
        import jax
        with jax.profiler.TraceAnnotation("bench.put"):
            b = self.put(self.n)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, metrics = self.compiled(self.state, b)
        self.n += 1
        return metrics


def first_steps(loop, on_first=None) -> tuple:
    """Drive the loop's own state through its first ``check_steps``
    steps and read what the check compares: the losses, the per-leaf
    norms of the first gradient as the optimizer got it (from AdamW's
    first moment after one step) and of the parameters' change since
    set-up. ``on_first(loop)``, where given, reads more after step 1."""
    import jax
    cfg, mix, fam = loop.ctx.config, loop.ctx.mix, loop.ctx.family
    b1 = mix["optimizer"]["b1"]
    norms = jax.jit(ref_train.leaf_norms)
    losses, grad1 = [], None
    for t in range(mix["check_steps"]):
        losses.append(float(loop.step()["loss"]))
        if t == 0:
            grad1 = {k: float(v) / (1 - b1) for k, v in
                     norms(loop.state["opt"]["m"]).items()}
            if on_first is not None:
                on_first(loop)
    change_fn = jax.jit(lambda p, kw: ref_train.leaf_norms(
        jax.tree.map(lambda a, b: a - b, p,
                     fam.pack_unrolled(fam.make(cfg, kw)))))
    change = {k: float(v) for k, v in
              change_fn(loop.state["params"], loop.k_weights).items()}
    return losses, grad1, change


def run(ctx) -> dict:
    import jax

    mix = ctx.mix
    loop = Loop(ctx)
    with jax.set_mesh(loop.mesh):
        losses, grad1, change = first_steps(loop)
        ctx.mark("first steps and their readings")
        tokens_per_step = mix["batch"] * mix["seq_len"]
        inflight = collections.deque()
        failed = attempted = 0
        tracer = ctx.tracer()
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_process
        ctx.in_window = True
        while True:
            now = time.perf_counter() - t0
            if now >= ctx.seconds:
                break
            tracer.tick(now)
            inflight.append(loop.step()["loss"])
            attempted += 1
            while len(inflight) > mix["in_flight"]:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    failed += not math.isfinite(float(inflight.popleft()))
        while inflight:
            failed += not math.isfinite(float(inflight.popleft()))
        jax.block_until_ready(loop.state)
        elapsed = time.perf_counter() - t0
        ctx.in_window = False
        tracer.stop()

    out = {
        "setup_s": setup_s,
        "attempted": attempted,
        "failed": failed,
        "metrics": {"train_tokens_per_s": attempted * tokens_per_step
                    / elapsed},
        "memory_peak_bytes": ctx.memory_peak(),
        "facts": {"chips": len(ctx.devices),
                  "hlo_text": loop.compiled.as_text() if ctx.trace else ""},
    }
    batches = check_batches(loop)
    del loop
    ref = reference(ctx, batches)
    numbers = compare_with(ref, losses, grad1, change)
    ctx.note(f"program losses {losses}, reference {ref['losses']}")
    ctx.note(f"leaves left out of change_gap (gradient under 1e-3 of the "
             f"median leaf's): {numbers.pop('n_leaves_left_out')}")
    out["checks"] = compare.judge(numbers, ctx.limits)
    return out


def check_batches(loop) -> list:
    return [(b["tokens"], b["labels"])
            for b in loop.batches[:loop.ctx.mix["check_steps"]]]


def reference(ctx, batches, *, precision: str = "float32", rows=None,
              keep_message: bool = False):
    """The plain reference's readings from the seed's weights over the
    first batches: in float32 (the yardstick), in a lower precision (the
    control) or with ``rows`` only (a planted fault)."""
    import jax
    canon = jax.jit(lambda k: ctx.family.make(ctx.config, k))(
        jax_key(ctx.seed, WEIGHTS_TAG))
    k_rng = jax_key(ctx.seed, RNG_TAG)
    keys = [jax.random.fold_in(k_rng, t) for t in range(len(batches))]
    return ref_train.run(ctx.family, ctx.config, ctx.mix, canon, batches,
                         keys, precision=precision, rows=rows,
                         n_steps=len(batches), keep_message=keep_message)


def compare_with(ref: dict, losses, grad1, change) -> dict:
    """The numbers compared, of readings against the float32 reference's.
    Leaves whose reference gradient is nought to rounding are left out of
    the change."""
    keep = compare.moving_leaves(ref["grad1"])
    return {
        "loss_gap": max(compare.rel_gap(a, b)
                        for a, b in zip(losses, ref["losses"])),
        "grad1_gap": compare.worst_leaf_gap(grad1, ref["grad1"]),
        "change_gap": compare.worst_leaf_gap(
            {k: change[k] for k in keep}, {k: ref["change"][k] for k in keep},
            keep=keep),
        "n_leaves_left_out": len(ref["grad1"]) - len(keep),
    }

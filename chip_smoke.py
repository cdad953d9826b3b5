"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py              one chip: train, codec and serve
    python chip_smoke.py --chips 4    four chips: the data-parallel trainer
                                      against the same batch on one device

One chip (every phase computes on the first device):
  train  ``repro.launch.train.main`` at full repro-100m width, batch 8 x
         1024 tokens, 5 steps, rq8 gradient compression with error
         feedback: every logged loss is finite, and the compiled step holds
         the Pallas codec (``tpu_custom_call``), not the jnp fallback.
  codec  ``flat_qdq`` over a buffer the size of the repro-100m gradient,
         Pallas against the jnp reference under the same key: bit-identical,
         or else no element more than one quantization level apart.
  serve  ``serve.run`` at full qwen1.5-0.5b width, 4 slots, 8 requests of
         32 prompt + 32 generated tokens: 8 of 8 answered, none dropped, and
         each first token is among the top 5 of a plain full-sequence
         forward of the same prompt.

Four chips (``--chips 4``, nothing else runs): the trainer on a (4, 1)
('data', 'model') mesh with the batch split over 'data', at ``none`` and at
rq8 with error feedback, and the ``none`` run again on one device. The
compiled four-chip steps must all-reduce, the batch must sit on four
devices, and the first three losses must agree with the one-device run.

A failure anywhere raises and exits non-zero; so does a process in which JAX
finds no TPU. Only a run that passed prints its last line, one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the compiled-HLO mark of a Pallas TPU kernel
PALLAS_MARK = "tpu_custom_call"
TRAIN_ARGV = ["--arch", "repro-100m", "--batch", "8", "--seq", "1024",
              "--log-every", "1"]
# first losses of the four-chip and one-device runs: the same math, summed
# in another order across devices
DP_LOSS_RTOL = 2e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")
    log(f"ok: {what}")


def phase_train(devices) -> int:
    """Full-width rq8 + error-feedback training through the CLI's main;
    returns the gradient's element count."""
    from repro.core.compression import FlatLayout
    from repro.launch import train
    t0 = time.time()
    run = train.main(TRAIN_ARGV + ["--steps", "5", "--compression", "rq8",
                                   "--error-feedback"], devices=devices)
    losses = [loss for _, loss in run.losses]
    check(len(losses) == 5 and all(math.isfinite(x) for x in losses),
          f"5 finite losses: {losses}")
    check(PALLAS_MARK in run.compiled_step.as_text(),
          "the compiled train step runs the Pallas codec")
    log(f"train phase {time.time() - t0:.1f}s")
    return FlatLayout.from_tree(run.state["params"]).total


def phase_codec(total: int):
    """Pallas flat_qdq against the jnp reference on `total` elements."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.quant import ops
    t0 = time.time()
    bits = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (total,), jnp.float32)
    key = jax.random.PRNGKey(2)
    got = ops.qdq_flat(x, key, bits=bits, backend="pallas")
    want = ops.qdq_flat(x, key, bits=bits, backend="jnp")
    check(got.shape == x.shape and bool(jnp.all(jnp.isfinite(got))),
          f"pallas flat_qdq gives {total} finite values")
    n_diff = int(jnp.sum(got != want))
    if n_diff == 0:
        log(f"codec: pallas == jnp bit for bit on {total} elements")
    else:
        # one quantization level is its bucket's (hi - lo) / levels
        _, cap, nb, _, _ = ops.flat_geometry(total, bits=bits)
        xb = ops.edge_pad(x, nb * cap).reshape(nb, cap)
        level = (xb.max(axis=1) - xb.min(axis=1)) / ((1 << bits) - 1)
        diff = jnp.pad(jnp.abs(got - want), (0, nb * cap - total))
        worst = float(jnp.max(diff.reshape(nb, cap).max(axis=1) / level))
        log(f"codec: {n_diff} of {total} elements differ, by at most "
            f"{worst:.4f} quantization levels")
        check(worst <= 1.0 + 1e-5, "pallas and jnp within one level")
    log(f"codec phase {time.time() - t0:.1f}s")


def phase_serve():
    """Full-width qwen1.5-0.5b through serve.run, checked against a plain
    full-sequence forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import serve
    from repro.models import transformer_scan
    t0 = time.time()
    cfg = serve.ServeConfig(arch="qwen1.5-0.5b", reduced=False, slots=4,
                            n_requests=8, prompt_len=32, gen_tokens=32)
    engine = serve.Engine(cfg)
    res = serve.run(cfg, engine=engine)
    print(serve.format_result(res), flush=True)
    check(res.n_completed == 8 and res.counters["dropped"] == 0
          and res.counters["rejected"] == 0,
          f"8 of 8 requests answered ({res.n_completed}), 0 dropped "
          f"({res.counters['dropped']}), 0 rejected")
    check(all(c.n_generated == 32 for c in res.completions.values()),
          "32 tokens generated per request")
    prompts = np.stack([r.tokens for r in serve.synthetic_requests(cfg)])
    logits, _ = jax.jit(lambda p, t: transformer_scan.apply(
        p, engine.model_cfg, {"tokens": t}, logits_positions="last"))(
        engine.params, jnp.asarray(prompts))
    top5 = np.asarray(jax.lax.top_k(logits[:, -1], 5)[1])
    first = [res.completions[i].tokens[0] for i in range(8)]
    n_top1 = sum(int(f == t[0]) for f, t in zip(first, top5))
    log(f"serve: first tokens {first}; {n_top1} of 8 are the reference's "
        f"argmax")
    check(all(f in t for f, t in zip(first, top5)),
          "every first token is in the reference forward's top 5")
    log(f"serve phase {time.time() - t0:.1f}s")


def phase_data_parallel(devices):
    """The (4, 1)-mesh trainer at none and rq8, and none on one device."""
    import numpy as np
    from repro.launch import train
    t0 = time.time()
    argv = TRAIN_ARGV + ["--steps", "3"]

    def spans(run, n_dev):
        shards = run.batch["tokens"].addressable_shards
        rows = run.batch["tokens"].shape[0] // n_dev
        return (len({s.device for s in shards}) == n_dev
                and all(s.data.shape[0] == rows for s in shards))

    four = train.main(argv + ["--compression", "none"], devices=devices)
    check("all-reduce" in four.compiled_step.as_text(),
          "the 4-chip step all-reduces")
    check(spans(four, len(devices)), "the batch is split over 4 devices")
    loss_four = [loss for _, loss in four.losses]
    del four
    one = train.main(argv + ["--compression", "none"], devices=devices[:1])
    loss_one = [loss for _, loss in one.losses]
    del one
    log(f"losses: 4 chips {loss_four}, 1 chip {loss_one}")
    check(len(loss_four) == 3 and np.allclose(loss_four, loss_one,
                                              rtol=DP_LOSS_RTOL, atol=0),
          f"4-chip and 1-chip losses agree to rtol {DP_LOSS_RTOL}")

    rq8 = train.main(argv + ["--compression", "rq8", "--error-feedback"],
                     devices=devices)
    hlo = rq8.compiled_step.as_text()
    losses = [loss for _, loss in rq8.losses]
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          f"rq8 on 4 chips: 3 finite losses {losses}")
    check(PALLAS_MARK in hlo and "all-reduce" in hlo,
          "the 4-chip rq8 step runs the Pallas codec and all-reduces")
    check(spans(rq8, len(devices)), "the rq8 batch is split over 4 devices")
    log(f"data-parallel phase {time.time() - t0:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.launch import compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU found: JAX's devices are {devices}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {compile_cache.use_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.time()
    if args.chips == 4:
        phase_data_parallel(devices[:4])
    else:
        total = phase_train(devices[:1])
        phase_codec(total)
        phase_serve()
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

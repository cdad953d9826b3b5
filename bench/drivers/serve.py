"""Serve loop: the program's continuous-batching engine, driven open-loop
through its public ``submit`` / ``step`` / ``result`` / ``counters``.

Requests come from ``generators/open_loop.py``. Times run from when each
request was due. The engine admits in FIFO order and every active request
gains one token per ``step``, so the harness derives each request's
first-token time and its token times from the steps, by the ``admitted``
counter: a request's first token is seen at the end of the step that
admitted it, and one more token at the end of every later step until its
completion appears. Requests due after the window keep the load on until
every request due inside it has finished.

The check: a sample of finished requests, drawn from the seed with the
longest among them, is run once through the plain reference over its
prompt and served tokens; the number compared is the widest gap by which
a served token's logit lies below the reference's best at that position.
"""
from __future__ import annotations

import collections
import math
import time

from bench.generators import open_loop
from bench.lib import compare, stats
from bench.lib import program  # noqa: F401  (puts the program on sys.path)
from bench.lib.seeds import jax_key, np_rng

WEIGHTS_TAG, ENGINE_TAG, CHECK_TAG = 0, 4, 5


class Rec:
    """The harness's record of one request."""

    def __init__(self, req):
        self.req = req
        self.rid = None
        self.first = None        # seconds after the window opened
        self.times = []          # end of each step in which it gained tokens
        self.tokens = None       # served tokens, once finished
        self.failed = False


def build_engine(ctx, mc):
    import jax
    from repro import serve
    from repro.models import transformer_scan
    cfg, mix, fam = ctx.config, ctx.mix, ctx.family
    k_w = jax_key(ctx.seed, WEIGHTS_TAG)
    params = jax.jit(lambda k: fam.pack_scanned(fam.make(cfg, k)))(k_w)
    jax.block_until_ready(params)
    theirs = jax.eval_shape(lambda k: transformer_scan.init(mc, k), k_w)
    if jax.tree.structure(theirs) != jax.tree.structure(params):
        raise ValueError("the program's parameter layout is not the one "
                         "the benchmark packs")
    sc = serve.ServeConfig(arch=mc.arch_id, reduced=False,
                           slots=mix["slots"], max_queue=mix["max_queue"],
                           max_len=mix["max_len"], window=0,
                           mode="continuous", temperature=0.0)
    return serve.Engine(sc, params=params, model_cfg=mc,
                        key=jax_key(ctx.seed, ENGINE_TAG))


def warm_up(engine, prompt_lens) -> None:
    """Compile every program the window will run: through the engine's
    own submit and step, one request of each prompt length in use (its
    prefill and the small per-length programs around it), the decode step
    and the splice into a slot."""
    import numpy as np
    for n in sorted(set(prompt_lens)):
        engine.submit(np.zeros(n, np.int32), 2)
    engine.run()


def run(ctx) -> dict:
    out, sample, misses = measure(ctx)
    numbers = dict(misses, served_logit_gap=served_gap(ctx, sample))
    out["checks"] = compare.judge(numbers, ctx.limits)
    return out


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def measure(ctx):
    """Set up, run the window and drain it; return the run's record, the
    check's sample of finished requests, and the counts of requests
    unanswered or answered with the wrong number of tokens. The engine
    is freed before this returns."""
    from repro import serve

    mix = ctx.mix
    mc = ctx.family.program_config(ctx.config)
    ctx.mark("imports and devices")
    engine = build_engine(ctx, mc)
    ctx.mark("weights and engine")
    sched = open_loop.schedule(mix, mc.vocab, ctx.seed, ctx.seconds)
    warm_up(engine, [len(r.prompt) for r in sched])
    ctx.mark("warm-up: compile or cache load, one request per length")

    recs = [Rec(r) for r in sched]
    fifo = collections.deque()
    active = {}
    # per step: (traced, prefill tokens, positions in use by the decode,
    # requests decoded, keys the prefilled tokens attended to)
    steps_log = []
    lateness = []
    counted_left = sum(r.counted for r in sched)
    backlog_at_close = None
    admitted_before = engine.counters["admitted"]
    tracer = ctx.tracer()
    i = 0
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    ctx.in_window = True
    while counted_left > 0:
        now = time.perf_counter() - t0
        if now > ctx.seconds + mix["drain_cap_s"]:
            break
        tracer.tick(now)
        if backlog_at_close is None and now >= ctx.seconds:
            backlog_at_close = len(fifo)
        while i < len(recs) and recs[i].req.due <= now:
            rec = recs[i]
            lateness.append(now - rec.req.due)
            try:
                with _span("bench.submit"):
                    rec.rid = engine.submit(rec.req.prompt, rec.req.max_new)
                fifo.append(rec)
            except serve.AdmissionError:
                rec.failed = True
                counted_left -= rec.req.counted
            i += 1
        if not fifo and not active:
            if i < len(recs):
                with _span("bench.wait_for_arrival"):
                    time.sleep(max(0.0, min(recs[i].req.due - now, 0.01)))
            continue
        with _span("bench.engine_step"):
            engine.step()
        t = time.perf_counter() - t0
        n_new = engine.counters["admitted"] - admitted_before
        admitted_before += n_new
        prefill_tokens = prefill_keys = 0
        for _ in range(n_new):
            rec = fifo.popleft()
            rec.first = t
            active[rec.rid] = rec
            p = len(rec.req.prompt)
            prefill_tokens += p
            prefill_keys += p * (p + 1) // 2
        positions = n_decoded = 0
        for rid, rec in list(active.items()):
            positions += len(rec.req.prompt) + 1 + len(rec.times)
            n_decoded += 1
            rec.times.append(t)
            done = engine.result(rid)
            if done is not None:
                rec.tokens = list(done.tokens)
                del active[rid]
                counted_left -= rec.req.counted
        steps_log.append((tracer.active, prefill_tokens, positions,
                          n_decoded, prefill_keys))
    ctx.in_window = False
    tracer.stop()

    counted = [r for r in recs if r.req.counted]
    unanswered = sum(r.tokens is None and not r.failed for r in counted)
    wrong_length = sum(r.tokens is not None and (
        len(r.tokens) != r.req.max_new or len(r.times) != r.req.max_new - 1)
        for r in counted)
    failed = sum(r.failed or r.tokens is None for r in counted)
    ttft = [(r.first - r.req.due) * 1e3 if r.first is not None else math.inf
            for r in counted]
    gaps = [(b - a) * 1e3 for r in counted for a, b in zip(r.times,
                                                          r.times[1:])]
    ctx.note(f"requests due in the window {len(counted)}, finished "
             f"{len(counted) - failed}, generator lateness median "
             f"{stats.nearest_rank(lateness, 50) * 1e3:.3f} ms max "
             f"{max(lateness) * 1e3:.3f} ms, inter-token gaps {len(gaps)}")
    traced = [s for s in steps_log if s[0]]
    out = {
        "setup_s": setup_s,
        "attempted": len(counted),
        "failed": failed,
        "metrics": {
            f"ttft_p{mix['ttft_quantile']}_ms":
                stats.nearest_rank(ttft, mix["ttft_quantile"]),
            f"itl_p{mix['itl_quantile']}_ms":
                stats.nearest_rank(gaps, mix["itl_quantile"]),
        },
        "memory_peak_bytes": ctx.memory_peak(),
        "facts": {"prefill_tokens": sum(s[1] for s in traced),
                  "prefill_keys": sum(s[4] for s in traced),
                  "decode_calls": sum(1 for s in traced if s[3]),
                  "decode_positions": sum(s[2] for s in traced),
                  "decode_tokens": sum(s[3] for s in traced),
                  "chips": len(ctx.devices),
                  "backlog_at_close": backlog_at_close,
                  "ttft_ms": ttft, "gaps_ms": gaps},
    }
    sample = pick_sample(ctx, counted)
    del engine
    return out, sample, {"unanswered": unanswered,
                         "wrong_length": wrong_length}


def pick_sample(ctx, counted) -> list:
    """Finished requests drawn from the seed, the longest first, until
    ``check_tokens`` served tokens are covered: [(prompt, tokens)]."""
    done = [r for r in counted if r.tokens]
    if not done:
        return []
    order = np_rng(ctx.seed, CHECK_TAG).permutation(len(done))
    longest = max(range(len(done)), key=lambda j: len(done[j].tokens))
    picked, n = [], 0
    for j in [longest] + [int(j) for j in order if j != longest]:
        picked.append((done[j].req.prompt, done[j].tokens))
        n += len(done[j].tokens)
        if n >= ctx.mix["check_tokens"]:
            break
    return picked


def gap_fn(fam, cfg: dict, max_len: int, control: str | None = None):
    """Jitted (weights, seq, pos, tok, mask) -> widest gap below the
    float32 reference's best logit: of the served tokens, or (with
    ``control``) of the tokens a lower precision puts first."""
    import jax
    import jax.numpy as jnp

    def fn(canon, seq, pos, tok, mask):
        z = fam.logits(cfg, canon, seq[None])[0][pos]          # (N, V)
        best = jnp.max(z, -1)
        if control is not None:
            zc = fam.logits(cfg, canon, seq[None],
                            precision=control)[0][pos]
            tok = jnp.argmax(zc, -1)
        g = best - jnp.take_along_axis(z, tok[:, None], 1)[:, 0]
        return jnp.max(jnp.where(mask, g, -jnp.inf))

    return jax.jit(fn)


def served_gap(ctx, sample, control: str | None = None) -> float:
    """Widest gap over the sample; ``inf`` where there is nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if not sample:
        return math.inf
    cfg, fam, L = ctx.config, ctx.family, ctx.mix["max_len"]
    canon = jax.jit(lambda k: fam.make(cfg, k))(jax_key(ctx.seed,
                                                        WEIGHTS_TAG))
    fn = gap_fn(fam, cfg, L, control)
    worst = -math.inf
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in sample:
            seq = np.zeros(L, np.int32)
            full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
            seq[: len(full)] = full
            n = len(tokens)
            pos = np.zeros(L, np.int32)
            tok = np.zeros(L, np.int32)
            mask = np.zeros(L, bool)
            pos[:n] = len(prompt) - 1 + np.arange(n)
            tok[:n] = tokens
            mask[:n] = True
            worst = max(worst, float(fn(canon, jnp.asarray(seq),
                                        jnp.asarray(pos), jnp.asarray(tok),
                                        jnp.asarray(mask))))
    return worst

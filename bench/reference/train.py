"""Reference of the first training steps: loss and gradient of the model
family's plain reference, global-norm clipping, the gradient message
through randomized quantization with error feedback, and AdamW under a
warm-up-then-cosine learning rate. Works on the family's canonical
weights; the gradient message is laid out as the family's unrolled
per-layer tree flattened in JAX's key order, which is the order of the
wire message."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import rq
from bench.reference.ops import cast


def lr_at(opt: dict, step):
    """Learning rate of 1-based ``step``: linear warm-up, then cosine
    down to ``floor`` of the peak."""
    s = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["lr"], opt["warmup"], opt["total"]
    cos = peak * (opt["floor"] + (1 - opt["floor"]) * 0.5 * (
        1 + jnp.cos(math.pi * jnp.clip((s - warm) / max(total - warm, 1),
                                        0.0, 1.0))))
    return jnp.where(s < warm, peak * s / max(warm, 1), cos)


def leaf_norms(tree) -> dict:
    """path -> L2 norm of each leaf of a parameter-layout tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def make_step(fam, cfg: dict, mix: dict, *, precision: str = "float32",
              rows=None):
    """One reference step of the model family ``fam`` as a jitted function
    (canon, m, v, err, step, tokens, labels, key) -> (loss, new canon,
    m, v, err, quantized gradient in the unrolled layout).

    ``rows`` (a slice) restricts the loss to some rows of the batch: a
    planted fault, a batch half left out or one chip's rows alone."""
    opt, codec = mix["optimizer"], mix["codec"]
    dt = cast(precision)

    def step_fn(canon, m, v, err, step, tokens, labels, qkey):
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        loss, g = jax.value_and_grad(
            lambda c: fam.loss(cfg, c, tokens, labels,
                               precision=precision))(canon)
        tree = fam.pack_unrolled(g)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                          for x in leaves))
        clip = jnp.minimum(1.0, mix["grad_clip"] / jnp.maximum(gn, 1e-9))
        flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32) * clip
                                for x in leaves])
        if codec["name"] != "none":
            vbuf = flat + err if codec["error_feedback"] else flat
            q = rq.qdq(vbuf, qkey, bits=codec["bits"],
                       bucket_elems=codec["bucket_elems"])
            if codec["error_feedback"]:
                err = vbuf - q
            flat = q
        sizes = np.cumsum([x.size for x in leaves])[:-1]
        gq_tree = jax.tree_util.tree_unflatten(treedef, [
            x.reshape(l.shape) for x, l in zip(jnp.split(flat, sizes),
                                              leaves)])
        gq = fam.unpack_unrolled(gq_tree)
        t = step + 1
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        eta = lr_at(opt, t)
        new_c, new_m, new_v = {}, {}, {}
        for k in canon:
            gk = gq[k].astype(dt)
            new_m[k] = (b1 * m[k] + (1 - b1) * gk).astype(dt)
            new_v[k] = (b2 * v[k] + (1 - b2) * gk * gk).astype(dt)
            mh = new_m[k] / (1 - b1 ** t)
            vh = new_v[k] / (1 - b2 ** t)
            u = mh / (jnp.sqrt(vh) + eps)
            if opt["weight_decay"]:
                u = u + opt["weight_decay"] * canon[k]
            new_c[k] = (canon[k] - eta * u).astype(dt)
        return loss.astype(jnp.float32), new_c, new_m, new_v, err, gq_tree

    return jax.jit(step_fn, donate_argnums=(0, 1, 2, 3))


def run(fam, cfg: dict, mix: dict, canon: dict, batches, keys, *,
        precision: str = "float32", rows=None, n_steps: int = 3,
        keep_message: bool = False) -> dict:
    """Follow ``n_steps`` steps from ``canon``; return the readings:
    losses, per-leaf norms of the first gradient as the optimizer gets it
    and per-leaf norms of the parameters' change after the last step.
    ``keep_message`` adds the first gradient message itself, on the host,
    flat in the order of the wire message."""
    dt = cast(precision)
    step = make_step(fam, cfg, mix, precision=precision, rows=rows)
    c = {k: jnp.array(x, dtype=dt, copy=True) for k, x in canon.items()}
    m = {k: jnp.zeros_like(x) for k, x in c.items()}
    v = {k: jnp.zeros_like(x) for k, x in c.items()}
    total = sum(x.size for x in canon.values())
    err = jnp.zeros((total,), jnp.float32)
    losses, g1, msg1 = [], None, None
    for t in range(n_steps):
        tok, lab = batches[t]
        loss, c, m, v, err, gq = step(c, m, v, err, t, tok, lab, keys[t])
        losses.append(float(loss))
        if t == 0:
            g1 = {k: float(x) for k, x in leaf_norms(gq).items()}
            if keep_message:
                msg1 = np.concatenate([np.asarray(x).reshape(-1) for x in
                                       jax.tree_util.tree_leaves(gq)])
    delta = fam.pack_unrolled({k: c[k].astype(jnp.float32) - canon[k]
                               for k in c})
    out = {"losses": losses, "grad1": g1,
           "change": {k: float(x) for k, x in leaf_norms(delta).items()}}
    if keep_message:
        out["message1"] = msg1
    return out

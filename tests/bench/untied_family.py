"""A dense decoder with an untied output head, as a model family of the
benchmark: ``test_bench_family.py`` adds it to a copy of the benchmark
by new files alone, as a configuration of a new architecture is added.

Layers, layouts and counts are the dense family's; the head is a weight
of its own, (d, vocab), the program's ``lm_head``, made after the dense
weights from the next ``fold_in`` of the key."""
import dataclasses

import jax
import jax.numpy as jnp

from bench.models import dense
from bench.reference.ops import DTYPES, cast, mm

tiny = dense.tiny
kv_bytes_per_position = dense.kv_bytes_per_position


def program_config(cfg: dict):
    tied = dense.program_config(dict(cfg, tie_word_embeddings=True))
    return dataclasses.replace(tied, tie_embeddings=False)


def make(cfg: dict, key):
    c = dense.make(cfg, key)
    m = dense.dims(cfg)
    z = jax.random.normal(jax.random.fold_in(key, len(c)), (m["d"], m["v"]),
                          jnp.float32)
    c["head"] = (z / jnp.sqrt(jnp.float32(m["d"]))).astype(
        DTYPES[cfg["torch_dtype"]]).astype(jnp.float32)
    return c


def pack_unrolled(c: dict) -> dict:
    return dict(dense.pack_unrolled(c), lm_head={"w": c["head"]})


def pack_scanned(c: dict) -> dict:
    return dict(dense.pack_scanned(c), lm_head={"w": c["head"]})


def unpack_unrolled(tree: dict) -> dict:
    return dict(dense.unpack_unrolled(tree), head=tree["lm_head"]["w"])


def _hidden(cfg, w, tokens, precision, remat=False):
    body = {k: v for k, v in w.items() if k != "head"}
    return dense.hidden(cfg, body, tokens, precision=precision, remat=remat)


def logits(cfg: dict, w: dict, tokens, *, precision="float32"):
    return mm("bsd,dv->bsv", _hidden(cfg, w, tokens, precision), w["head"],
              precision)


def loss(cfg: dict, w: dict, tokens, labels, *, precision="float32"):
    x = _hidden(cfg, w, tokens, precision, remat=True)
    z = mm("bsd,dv->bsv", x, w["head"], precision).astype(cast(precision))
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def n_params(cfg: dict) -> int:
    """The dense count and the head's own d x vocab."""
    m = dense.dims(cfg)
    return dense.n_params(cfg) + m["d"] * m["v"]


def model_flops_per_token(cfg: dict, seq_len: int) -> int:
    """The dense count: it counts the head's product once, and the
    embedding, a lookup, not at all, which holds here too."""
    return dense.model_flops_per_token(cfg, seq_len)

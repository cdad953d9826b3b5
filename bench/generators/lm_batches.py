"""Training batches: a learnable bigram token stream.

Each token prefers ``n_succ`` successors from a fixed random table; with
probability ``noise`` it is replaced by a uniform token. Made on the
device in one call during set-up, then fetched to the host, so that the
window puts one batch per step as a data loader would. The same seed gives
the same batches, and every batch differs from every other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.seeds import jax_key, np_rng


def make(mix: dict, vocab: int, seed: int) -> list:
    """``mix["distinct_batches"]`` batches of ``{"tokens", "labels"}``,
    each (batch, seq) int32 numpy arrays."""
    b, s = mix["batch"], mix["seq_len"]
    n, n_succ = mix["distinct_batches"], mix["n_succ"]
    succ = jnp.asarray(np_rng(seed, 1).integers(0, vocab, (vocab, n_succ)),
                       jnp.int32)

    # the table is an argument, not a constant of the program, so that
    # every seed runs the same compiled program
    @jax.jit
    def gen(key, succ):
        def one(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            first = jax.random.randint(k1, (b,), 0, vocab)
            choice = jax.random.randint(k2, (s + 1, b), 0, n_succ)
            noisy = jax.random.bernoulli(k3, mix["noise"], (s + 1, b))
            rand = jax.random.randint(k4, (s + 1, b), 0, vocab)

            def step(tok, inp):
                c, z, r = inp
                nxt = jnp.where(z, r, succ[tok, c])
                return nxt, nxt

            _, seq = jax.lax.scan(step, first, (choice, noisy, rand))
            return seq.T                                   # (b, s + 1)
        return jax.vmap(one)(jax.random.split(key, n))

    seqs = np.asarray(gen(jax_key(seed, 2), succ))
    return [{"tokens": q[:, :-1], "labels": q[:, 1:]} for q in seqs]

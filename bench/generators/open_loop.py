"""Open-loop request schedule for a serving cell.

Arrivals are a Poisson process at ``rate_per_s``; prompt lengths are
lognormal and rounded up to the next of ``prompt_buckets`` (every distinct
length is a separately compiled prefill program); output lengths are
lognormal, clipped. Token ids are uniform over the vocabulary.

So that every seed offers the same work, the schedule (the gaps between
arrivals and each request's prompt and output length, in order) is drawn
once per window length from the mix's ``pool_seed``; ``--seed`` draws the
token ids (and, in the driver, the weights). With a window of some tens of
requests, reordering them by seed moved the queueing, and so the latency
tails, far more than the program's own run-to-run noise. The
``n_window`` requests due in the window fill it exactly; as many again
follow it, to keep the load on while the requests of the window finish.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.seeds import np_rng


@dataclasses.dataclass
class Request:
    idx: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (prompt_len,) int32
    max_new: int
    counted: bool           # due inside the window


def _lognormal(rng, median, sigma, n):
    return median * np.exp(sigma * rng.standard_normal(n))


def pool(mix: dict, seconds: float):
    """The fixed sizes and gaps of one window length: (gaps, prompt lens,
    output lens), each 2 * n_window long."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = np.random.default_rng(mix["pool_seed"] + int(round(seconds * 1000)))
    gaps = rng.exponential(1.0 / mix["rate_per_s"], 2 * n)
    gaps[:n] *= seconds / gaps[:n].sum()      # n arrivals fill the window
    buckets = np.asarray(mix["prompt_buckets"])
    p = _lognormal(rng, mix["prompt_median"], mix["prompt_sigma"], 2 * n)
    plens = buckets[np.minimum(np.searchsorted(buckets, p),
                               len(buckets) - 1)]
    o = _lognormal(rng, mix["output_median"], mix["output_sigma"], 2 * n)
    olens = np.clip(np.ceil(o), mix["output_min"], mix["output_max"])
    return gaps, plens.astype(int), olens.astype(int), n


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> list:
    gaps, plens, olens, n = pool(mix, seconds)
    rng = np_rng(seed, 3)
    out = []
    for half in (slice(0, n), slice(n, 2 * n)):
        start = 0.0 if half.start == 0 else seconds
        # the first request of the window is due at its opening
        dues = start + np.concatenate([[0.0], np.cumsum(gaps[half])[:-1]])
        for due, p, o in zip(dues, plens[half], olens[half]):
            out.append(Request(len(out), float(due),
                               rng.integers(0, vocab, int(p), dtype=np.int32),
                               int(o), half.start == 0))
    return out

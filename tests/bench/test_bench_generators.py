"""Traffic generators: the same seed gives the same inputs, and every seed
offers the same work."""
import json

import numpy as np
from conftest import SERVE_MIX

from bench.generators import lm_batches, open_loop
from bench.lib.spec import BENCH

BIG_SEED = 2**31 + 12345


def _mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def test_lm_batches_repeat_per_seed_and_differ_between_batches():
    mix = dict(_mix("lm8x1024.rq8ef"), batch=4, seq_len=16,
               distinct_batches=3)
    a = lm_batches.make(mix, 64, BIG_SEED)
    b = lm_batches.make(mix, 64, BIG_SEED)
    c = lm_batches.make(mix, 64, BIG_SEED + 1)
    assert len(a) == 3
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
    assert a[0]["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(a[0]["tokens"][:, 1:],
                                  a[0]["labels"][:, :-1])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert all(int(x["tokens"].max()) < 64 for x in a)


def test_open_loop_repeats_per_seed():
    mix = SERVE_MIX
    a = open_loop.schedule(mix, 1000, BIG_SEED, 20.0)
    b = open_loop.schedule(mix, 1000, BIG_SEED, 20.0)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_offers_every_seed_the_same_work():
    """Every seed gets the same schedule of arrivals and lengths; the seed
    draws the token ids."""
    mix = SERVE_MIX
    a = open_loop.schedule(mix, 1000, 7, 30.0)
    b = open_loop.schedule(mix, 1000, BIG_SEED, 30.0)
    n = int(round(mix["rate_per_s"] * 30.0))
    for s in (a, b):
        counted = [r for r in s if r.counted]
        assert len(counted) == n and len(s) == 2 * n
        assert all(0.0 <= r.due < 30.0 for r in counted)
        assert all(r.due >= 30.0 for r in s if not r.counted)
        assert all(len(r.prompt) in mix["prompt_buckets"] for r in s)
        assert all(mix["output_min"] <= r.max_new <= mix["output_max"]
                   for r in s)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)

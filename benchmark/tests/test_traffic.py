"""The generator: one seed one stream; another seed the same trace (lengths
and their order) with other ids."""

import itertools
import json
import os

import numpy as np

from benchmark import traffic

WL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "workloads")


def params():
    with open(os.path.join(WL, "serve-xl-decode.json")) as f:
        return json.load(f)["traffic_params"]


def take(seed, n):
    return list(itertools.islice(traffic.requests(params(), seed, 50304), n))


def test_same_seed_same_stream():
    a, b = take(7, 40), take(7, 40)
    for x, y in zip(a, b):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert x["max_new_tokens"] == y["max_new_tokens"]


def _lengths(reqs):
    return [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]


def test_other_seed_same_trace_other_ids():
    n = params()["pool"]
    a, b = take(7, 3 * n), take(2**31 + 5, 3 * n)
    assert _lengths(a) == _lengths(b)
    assert not np.array_equal(a[0]["prompt"][:8], b[0]["prompt"][:8])


def test_every_pass_holds_the_pool_in_an_order_of_its_own():
    n = params()["pool"]
    passes = [_lengths(take(7, 3 * n))[i * n:(i + 1) * n] for i in range(3)]
    assert sorted(passes[0]) == sorted(passes[1]) == sorted(passes[2])
    assert passes[0] != passes[1] != passes[2]


def test_another_sizes_seed_is_another_trace():
    p = dict(params(), sizes_seed=params()["sizes_seed"] + 1)
    other = list(itertools.islice(traffic.requests(p, 7, 50304), 16))
    assert _lengths(other) != _lengths(take(7, 16))


def test_lengths_keep_to_the_cell_and_nothing_is_shared():
    p = params()
    reqs = take(3, 3 * p["pool"])
    for r in reqs:
        assert p["prompt_len"]["min"] <= len(r["prompt"]) <= p["prompt_len"]["max"]
        assert p["output_len"]["min"] <= r["max_new_tokens"] <= p["output_len"]["max"]
        # prompt + output fits 48 pages of 16: no preemption in 384 pages
        assert len(r["prompt"]) + r["max_new_tokens"] <= 768
        assert r["prompt"].max() < 50303  # the last id is the warm-up's
    firsts = [int(r["prompt"][0]) for r in reqs]
    assert len(set(firsts)) == len(firsts)
    # a pair that comes round again comes with fresh ids
    again = [r for r in reqs[1:] if _lengths([r]) == _lengths(reqs[:1])]
    assert again and not np.array_equal(reqs[0]["prompt"], again[0]["prompt"])

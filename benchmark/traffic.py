"""The one traffic generator. A cell's ``traffic_params`` are data; this
turns them and the seed into requests. The trace — the pool of (prompt
length, output length) pairs and the order they come in — is drawn from the
cell's ``sizes_seed`` alone; the run's seed gives the token ids (and, in the
kinds, the weights). A seed must not change the work: under a closed loop
that keeps prefill saturated the order alone moves tokens/s by 11 % between
seeds (PERF.md, section 2), so the order belongs to the cell. Another trace
is another cell: a data file with another ``sizes_seed``."""

from __future__ import annotations

import math
import typing as tp

import numpy as np


def _lengths(rng, n: int, spec) -> np.ndarray:
    """``n`` lengths from ``spec``: {"dist": "lognormal", "median", "sigma",
    "min", "max"} or {"dist": "fixed", "value"}."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def requests(params, seed: int, vocab: int):
    """An endless stream of requests {"prompt": int32 ids, "max_new_tokens"}.
    The ``pool`` length pairs come round again and again, every pass in an
    order of its own (a fixed permutation repeated would make the stream
    periodic, and a closed loop locks onto a period), each time with fresh
    token ids, uniform over ``[0, vocab - 1)``:
    the last id is kept for the harness's warm-up request. With
    ``distinct_first_token`` no two prompts of a run start alike, so nothing
    is shared and the prefix cache never hits; with ``shared_prefix`` > 0
    every prompt starts with the same that many ids."""
    n = int(params["pool"])
    fixed = np.random.default_rng(int(params.get("sizes_seed", 0)))
    plen = _lengths(fixed, n, params["prompt_len"])
    olen = _lengths(fixed, n, params["output_len"])
    rng = np.random.default_rng([int(seed), 0x5E2E])
    shared = rng.integers(0, vocab - 1, size=int(params.get("shared_prefix", 0)))
    firsts = rng.permutation(vocab - 1)
    i = 0
    while True:
        if i % n == 0:
            order = fixed.permutation(n)
        j = order[i % n]
        ids = rng.integers(0, vocab - 1, size=int(plen[j]))
        if len(shared):
            ids[: len(shared)] = shared[: len(ids)]
        elif params.get("distinct_first_token"):
            ids[0] = firsts[i % len(firsts)]
        yield {"prompt": ids.astype(np.int32), "max_new_tokens": int(olen[j])}
        i += 1

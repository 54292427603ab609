"""The comparison has been shown to fail: the control (the reference put in
the program's place, computed in the precision below the stated one) and
each fault a cell can have come out as not correct; the program itself comes
out correct (test_cells). The faults are planted UNDER the harness, in the
program's own timed path, and the whole run is driven but for its look for
a chip."""

import contextlib
import io
import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark import spec as specs
from benchmark.tests import tiny


def drive(copy, monkeypatch, workload, *extra, seed=21):
    """``benchmark.run.main`` in this process, on the copy's files."""
    monkeypatch.setattr(specs, "ROOT", copy)
    monkeypatch.setattr(specs, "HERE", os.path.join(copy, "benchmark"))
    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", "1", "--trace", "0", "--rehearsal",
                             *extra])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell,stand_in", [
    ("tiny-train", "fp8"),          # the control: below the stated bf16
    ("tiny-train", "half_batch"),   # planted in the reference's stand-in
    ("tiny-train", "frozen"),
    ("tiny-serve", "ref_int4"),     # the control at this width, not decoding
    ("tiny-serve", "altered_token"),
])
def test_stand_in_is_not_correct(copy, monkeypatch, cell, stand_in):
    res = drive(copy, monkeypatch, cell, "--stand-in", stand_in)
    assert res["correct"] is False
    over = [n for n, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over, res["compared"]


def _break_window(monkeypatch, breaker):
    import midgpt_tpu.train as train_mod

    real = train_mod.get_train_window

    def broken(cfg, mesh, k):
        prog = real(cfg, mesh, k)
        return lambda state, xs, ys, key: breaker(prog, state, xs, ys, key)

    monkeypatch.setattr(train_mod, "get_train_window", broken)


def test_step_that_returns_its_state_unchanged(copy, monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen(prog, state, xs, ys, key):
        _, out = prog(jax.tree.map(jnp.copy, state), xs, ys, key)
        return state, out

    _break_window(monkeypatch, frozen)
    res = drive(copy, monkeypatch, "tiny-train")
    assert res["correct"] is False
    assert res["compared"]["change_leaf_gap"]["value"] == pytest.approx(1.0)
    assert res["compared"]["moment_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(copy, monkeypatch):
    import jax.numpy as jnp

    def half(prog, state, xs, ys, key):
        # rows of the first half stand in for the second: the mean is taken
        # over the first half alone
        b = xs.shape[2] // 2
        xs = jnp.concatenate([xs[:, :, :b], xs[:, :, :b]], axis=2)
        ys = jnp.concatenate([ys[:, :, :b], ys[:, :, :b]], axis=2)
        return prog(state, xs, ys, key)

    _break_window(monkeypatch, half)
    res = drive(copy, monkeypatch, "tiny-train")
    assert res["correct"] is False


def test_token_altered_where_it_is_produced(copy, monkeypatch):
    from midgpt_tpu.serving import ServingEngine

    real = ServingEngine.step
    touched = set()

    def step(self):
        more = real(self)
        for rid, req in self.finished.items():
            if rid not in touched and len(req.tokens) > 2:
                touched.add(rid)
                i = len(req.tokens) // 2
                req.tokens[i] = (req.tokens[i] + 1) % 511
        return more

    monkeypatch.setattr(ServingEngine, "step", step)
    res = drive(copy, monkeypatch, "tiny-serve")
    assert touched
    assert res["correct"] is False
    assert res["compared"]["served_logit_gap"]["value"] > 0.04

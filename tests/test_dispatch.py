"""Dispatch/launch auditor tests (analysis/dispatch.py and the one
dispatch budget of analysis/budgets.py).

Every serving program of every model family the benchmark serves must be
ONE launch a window, carry no host transfer, and inline exactly one
attention body per attention layer. The byte budgets cannot see a
violation of any of the three (none moves a byte at the entry interface),
so the static trace is where it is caught. The window-ahead order of the
engine (a window enqueued before the host reads the last one) relies on
no window syncing with the host.
"""

import jax
import jax.numpy as jnp
import pytest

from midgpt_tpu.analysis.budgets import check_dispatch_budget, dispatch_budget
from midgpt_tpu.analysis.dispatch import dispatch_report
from midgpt_tpu.analysis.harness import audit_serving_dispatch
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import engine as eng
from midgpt_tpu.serving import paged
from midgpt_tpu.serving.paged import PagedKVPool, pages_needed

SLOTS, WINDOW, PAGE, CHUNK = 2, 4, 16, 16
sds = jax.ShapeDtypeStruct
i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
flag = lambda *shape: sds(shape, jnp.bool_)  # noqa: E731


def _trace(cfg, name):
    """``(jaxpr, window_steps)`` of one serving program of a model of
    ``cfg``, traced through the engine's own factory with
    ``paged_kernel="pallas"`` (what ``auto`` resolves to on a TPU):
    shapes only, nothing compiles."""
    pmax = pages_needed(cfg.block_size, PAGE)
    model = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: PagedKVPool.init(cfg, 8, PAGE))
    logits = sds((SLOTS, cfg.vocab_size), jnp.float32)
    hybrid = cfg.linear_layers > 0
    state = ((jax.eval_shape(
        lambda: paged.RecurrentState.init(cfg, SLOTS)),) if hybrid else ())
    geom = dict(pmax=pmax, rope_len=cfg.block_size, paged_kernel="pallas")
    s = SLOTS
    if name == "prefill_chunk":
        fn = eng.make_prefill_chunk_program(
            model, chunk_len=CHUNK, pmax=pmax, rope_len=cfg.block_size)
        args = (model, pool, logits, i32(), i32(1, CHUNK), i32(), i32(),
                i32(pmax), *state, *((flag(),) if hybrid else ()))
        steps = 1
    elif name == "block_window":
        b = cfg.block_len
        fn = eng.make_block_window(model, slots=s, window=WINDOW, **geom)
        args = (model, pool, i32(s, pmax), i32(s), flag(s), i32(s), i32(s),
                i32(s), i32(s, b), flag(s, b), i32(s, b), flag(s), i32(s, b))
        steps = WINDOW
    elif name == "decode_window":
        fn = eng.make_decode_window(model, slots=s, window=WINDOW, **geom)
        args = (model, pool, logits, i32(s, pmax), i32(s), flag(s), i32(s),
                i32(s), i32(s), i32(s), sds((2,), jnp.uint32), *state)
        steps = WINDOW
    else:  # verify
        fn = eng.make_verify_program(model, slots=s, spec_len=4, **geom)
        args = (model, pool, logits, i32(s, pmax), i32(s), flag(s), i32(s),
                i32(s), i32(s), i32(s, 4), i32(s))
        steps = 1
    return jax.make_jaxpr(fn)(*args), steps


@pytest.mark.parametrize("family,name", [
    ("dense", "decode_window"),
    ("dense", "prefill_chunk"),
    ("dense", "verify"),
    ("block", "block_window"),
    ("block", "prefill_chunk"),
    ("gated_delta", "decode_window"),
    ("gated_delta", "prefill_chunk"),
    ("latent", "decode_window"),
    ("latent", "prefill_chunk"),
    ("mamba", "decode_window"),
    ("mamba", "prefill_chunk"),
])
def test_dispatch_budget_holds_for_every_serving_program(
        family_config, family, name):
    cfg = family_config(family)
    jaxpr, steps = _trace(cfg, name)
    rep = dispatch_report(jaxpr, program=name, window_steps=steps)
    assert rep.launches_per_window == 1
    assert rep.host_transfers == 0
    assert rep.inlined_layer_bodies == cfg.kv_layers > 0
    assert not check_dispatch_budget(rep, dispatch_budget(cfg))


def test_dispatch_budget_catches_a_split_window_and_a_lost_layer(
        family_config):
    """The rule is not vacuous: a window that covers half its steps in
    one launch, and a trace with one attention body fewer than the
    model's attention layers, each fail it by name."""
    import dataclasses

    cfg = family_config("dense")
    jaxpr, _ = _trace(cfg, "decode_window")
    rep = dispatch_report(jaxpr, program="decode_window",
                          window_steps=2 * WINDOW)
    deeper = dataclasses.replace(cfg, n_layer=cfg.n_layer + 1)
    bad = check_dispatch_budget(rep, dispatch_budget(deeper))
    assert [v.split(" ")[1] for v in bad] == [
        "launches_per_window", "inlined_layer_bodies"]


def test_dispatch_sees_callbacks_inside_cond_branches():
    """The host-transfer gate must not be blind to sub-jaxprs stored in
    TUPLE params: ``lax.cond``'s branches are a plain tuple of
    ClosedJaxprs, which a bare hasattr walk over params.values() skips
    — a callback hidden in a branch would pass the budget vacuously
    (caught in code review)."""

    def traced(x):
        def branch(v):
            jax.debug.callback(lambda a: None, v)
            return v * 2.0

        return jax.lax.cond(x[0] > 0, branch, lambda v: v, x)

    cj = jax.make_jaxpr(traced)(jnp.zeros((2,), jnp.float32))
    rep = dispatch_report(cj, program="probe")
    assert rep.host_transfers >= 1


def test_audit_serving_dispatch_end_to_end():
    reports, violations = audit_serving_dispatch("openwebtext")
    assert set(reports) == {
        "decode_window", "prefill_chunk", "verify_program"
    }
    assert violations == []

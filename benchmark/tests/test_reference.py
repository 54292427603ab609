"""The plain reference against the program at a tiny size, both in float32:
the same weights and tokens give the same logits and the same first
gradients."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import program, reference, weights
from benchmark.tests.tiny import TINY_SIZES


def test_logits_and_gradients_agree():
    sizes = TINY_SIZES
    key = weights.key_of(2**31 + 3)
    w = weights.make(key, sizes, jnp.float32)
    mcfg = program.model_config(sizes, {"attn_impl": "naive"})
    model = program.fill_model(w, mcfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, sizes["vocab_size"], (4, 128)), jnp.int32)
    y = jnp.roll(x, -1, axis=1)

    with jax.default_matmul_precision("highest"):
        got = model(x)
    want = reference.logits_of(w, reference.hidden(w, x, sizes))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4

    def program_loss(m):
        from midgpt_tpu.train import loss_fn

        return loss_fn(m, x, y, None, True)

    with jax.default_matmul_precision("highest"):
        g_prog = program.model_leaves(jax.grad(program_loss)(model))
    g_ref = jax.grad(reference.loss)(w, x, y, sizes)
    for name in weights.LEAVES:
        a, b = np.asarray(g_prog[name]), np.asarray(g_ref[name])
        assert np.linalg.norm(a - b) <= 1e-3 * np.linalg.norm(b) + 1e-9, name


def test_weights_are_the_seeds():
    a = weights.make(weights.key_of(5), TINY_SIZES, jnp.float32)
    b = weights.make(weights.key_of(5), TINY_SIZES, jnp.float32)
    c = weights.make(weights.key_of(2**31 + 5), TINY_SIZES, jnp.float32)
    for k in weights.LEAVES:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["wqkv"]), np.asarray(c["wqkv"]))
    # one leaf alone is the leaf of the whole
    one = weights.leaf("w_up", weights.key_of(5), TINY_SIZES, jnp.float32)
    assert np.array_equal(np.asarray(one), np.asarray(a["w_up"]))
    assert np.array_equal(np.asarray(a["lm_head"]), np.asarray(a["wte"]).T)

"""The gated delta rule's two forms (``ops/gated_delta``) against the
recurrence taken a token at a time, at a small size (4 heads, keys of 8,
values of 16) on seeded draws: the chunked form at several chunk sizes and a
length that is no multiple of any, at the corners of its gates; the one-token
form carried over a sequence; the Pallas step kernel through the interpreter
against the ``jax.numpy`` body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.ops import gated_delta as gd

B, T, H, DK, DV = 2, 150, 4, 8, 16


def draws(seed=0, t=T, decay="mixed", beta_top=2.0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, t, H, DK))) / np.sqrt(DK)
    k = unit(jax.random.normal(ks[1], (B, t, H, DK)))
    v = jax.random.normal(ks[2], (B, t, H, DV))
    u = jax.random.uniform(ks[3], (B, t, H))
    g = {
        "mixed": -2.0 * u,           # alpha from 0.14 to 1
        "near_one": -1e-4 * u,       # alpha within 1e-4 of 1: nothing fades
        "near_zero": -8.0 - 4.0 * u,  # alpha under 4e-4: nothing is kept
    }[decay]
    beta = beta_top * (1.0 - 1e-3 * jax.random.uniform(ks[4], (B, t, H)))
    if beta_top == 0.0:  # all of (0, 2)
        beta = 2.0 * jax.random.uniform(ks[4], (B, t, H))
    s0 = jax.random.normal(ks[5], (B, H, DK, DV))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta, s0)


@pytest.mark.parametrize("chunk", [1, 16, 64])
@pytest.mark.parametrize("decay,beta_top", [
    ("mixed", 0.0), ("near_one", 2.0), ("near_zero", 2.0), ("mixed", 2.0),
])
def test_chunked_is_the_recurrence(chunk, decay, beta_top):
    """150 tokens are no multiple of 16 or 64. With beta within a thousandth
    of 2 a step reflects the state along k and nothing damps an error but the
    decay: float32 sums in another order stay within 1e-4 of the state's
    scale all the same."""
    args = draws(1, decay=decay, beta_top=beta_top)
    o_r, s_r = gd.recurrent(*args)
    o_c, s_c = jax.jit(lambda *a: gd.chunked(*a, chunk=chunk))(*args)
    scale = float(jnp.abs(s_r).max()) + 1.0
    np.testing.assert_allclose(o_c, o_r, atol=2e-4 * scale, rtol=0)
    np.testing.assert_allclose(s_c, s_r, atol=2e-4 * scale, rtol=0)


def test_chunked_carries_state_from_call_to_call():
    """A sequence in three calls (the prefill's chunks), each from the state
    the last returned, is the sequence in one."""
    q, k, v, g, beta, s0 = draws(2)
    o_one, s_one = gd.chunked(q, k, v, g, beta, s0)
    outs, s = [], s0
    for lo, hi in ((0, 37), (37, 101), (101, T)):
        o, s = gd.chunked(*(a[:, lo:hi] for a in (q, k, v, g, beta)), s)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), o_one, atol=1e-4)
    np.testing.assert_allclose(s, s_one, atol=1e-4)


def test_masked_tokens_leave_the_state_to_the_bit():
    """g = 0 and beta = 0: how padding and idle slots are masked."""
    q, k, v, g, beta, s0 = draws(3, t=5)
    o, s = gd.chunked(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), s0)
    np.testing.assert_array_equal(s, s0)
    stack = s0[None]
    _, new = gd.step(q[:, 0], k[:, 0], v[:, 0], jnp.zeros((B, H)),
                     jnp.zeros((B, H)), stack, 0)
    np.testing.assert_array_equal(new, stack)


def test_step_over_a_sequence_is_chunked():
    q, k, v, g, beta, s0 = draws(4, t=70)
    o_c, s_c = gd.chunked(q, k, v, g, beta, s0)
    stack = jnp.stack([jnp.zeros_like(s0), s0])  # the layer is row 1
    one = jax.jit(lambda *a: gd.step(*a, 1))
    outs = []
    for t in range(70):
        o, stack = one(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], stack)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_c, atol=1e-4)
    np.testing.assert_allclose(stack[1], s_c, atol=1e-4)
    np.testing.assert_array_equal(stack[0], 0.0)  # the other layer's rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_step_kernel_interpreted_is_the_numpy_body(dtype):
    """The Pallas kernel's two contractions are the reference's
    broadcast-multiplies and sums: equal to float32's last bits, in place
    in the stack, and the other layers' rows untouched."""
    q, k, v, g, beta, s0 = draws(5, t=1, dtype=dtype)
    stack = jnp.stack([s0 + 1.0, s0, s0 - 1.0])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], stack, 1)
    assert gd.kernel_shapes_ok(H, DK, 64) and not gd.kernel_shapes_ok(H, 7, 64)
    o_k, new_k = gd._STEP_CALL(*args[:6], layer=1, interpret=True)
    o_x, new_x = gd.step(*args)  # the CPU takes the jax.numpy body
    np.testing.assert_allclose(o_k, o_x, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(new_k, new_x, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new_k[0], stack[0])
    np.testing.assert_array_equal(new_k[2], stack[2])

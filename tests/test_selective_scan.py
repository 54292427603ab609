"""The Mamba-1 mixer (``models.gpt.Mamba``) and its selective scan
(``ops/selective_scan``) at a small size — D 32, E 64, N 16, R 8, taps 4 —
on seeded draws: the whole-sequence forward against the layer's equations
worked token by token in numpy; a prompt in chunks of unequal length and then
decode steps against the whole sequence, state and tail included; both call
shapes of the Pallas kernel through the interpreter against the
``jax.numpy`` recurrence; the configuration's plan and the state's layout;
the parameter count at the published widths."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT, Mamba
from midgpt_tpu.ops import selective_scan as ss
from midgpt_tpu.serving.paged import RecurrentState

D, E, N, R, TAPS = 32, 64, 16, 8, 4
CFG = ModelConfig(
    block_size=128, vocab_size=512, n_layer=3, n_head=4, n_kv_head=1,
    n_embd=D, head_width=8, mlp="swiglu", mlp_hidden=64, qk_norm=False,
    rope_style="none", norm_scale=True, norm_eps=1e-6, tie_embeddings=True,
    layer_types=("mamba", "full_attention", "mamba"), mamba_d_state=N,
    mamba_expand=E // D, mamba_dt_rank=R, mamba_d_conv=TAPS,
    attn_impl="naive", remat="none",
)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mixer(seed=0) -> Mamba:
    """A Mamba layer with every leaf drawn (norm scales and the conv's bias
    away from their inits, so that each shows)."""
    m = Mamba.init(jax.random.PRNGKey(seed), CFG)
    leaves, tree = jax.tree.flatten(m)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for k, a in zip(ks, leaves):
        if a.ndim == 1 and a.shape != (E,):  # the inner norms' scales
            a = a + 0.1 * jax.random.normal(k, a.shape)
        elif a.shape == (E,) and bool(jnp.all(a == 0.0)):  # conv bias
            a = 0.2 * jax.random.normal(k, a.shape)
        out.append(a)
    return jax.tree.unflatten(tree, out)


def by_hand(m: Mamba, u: np.ndarray) -> np.ndarray:
    """The layer's equations (ISSUE 42) worked a token at a time in numpy,
    float64: ``u`` ``[T, D]`` -> ``[T, D]``."""
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), m)

    def rms(x, s):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * s

    def silu(x):
        return x / (1.0 + np.exp(-x))

    xz = u @ w.w_in.weight
    x, z = xz[:, :E], xz[:, E:]
    h = np.zeros((E, N))
    tail = np.zeros((TAPS - 1, E))
    out = []
    for t in range(u.shape[0]):
        win = np.concatenate([tail, x[t:t + 1]])
        xc = silu((w.conv * win).sum(0) + w.conv_bias)
        tail = win[1:]
        p = xc @ w.w_x.weight
        dt = rms(p[:R], w.dt_norm.weight)
        b = rms(p[R:R + N], w.b_norm.weight)
        c = rms(p[R + N:], w.c_norm.weight)
        delta = np.log1p(np.exp(dt @ w.w_dt.weight + w.dt_bias))
        a = -np.exp(w.a_log)
        h = np.exp(delta[:, None] * a) * h + (delta * xc)[:, None] * b[None]
        y = h @ c + w.d * xc
        out.append((y * silu(z[t])) @ w.w_out.weight)
    return np.stack(out)


def test_whole_sequence_is_the_equations_by_hand():
    """float32 against float64, 37 tokens: what is left is rounding."""
    m = mixer()
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 37, D))
    got = np.asarray(jax.jit(lambda m, u: m(u))(m, u))[0]
    want = by_hand(m, np.asarray(u, np.float64)[0])
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=0)


def test_chunks_then_steps_are_the_whole_sequence():
    """A prompt of 30 in chunks of 13 and 17 tokens, each right-padded to 16
    and 32 rows, then 7 decode steps among three slots of which one is idle:
    the outputs, the state and the tail are those of the whole sequence, and
    neither the padding nor the idle slot moves anything."""
    m = mixer(1)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 37, D))
    valid = jnp.array([False, True, False])
    whole = np.asarray(jax.jit(lambda m, u: m(u))(m, u))
    prefill = jax.jit(lambda m, *a: m.prefill_at(*a))
    decode = jax.jit(lambda m, *a: m.decode_at(*a, 0, valid))
    st = RecurrentState.init(CFG, 1, jnp.float32)
    s, tail = st.s[0], st.conv[0]
    outs = []
    for lo, hi, rows in ((0, 13, 16), (13, 30, 32)):
        x = jnp.zeros((1, rows, D)).at[:, : hi - lo].set(u[:, lo:hi])
        o, s, tail = prefill(m, x, s, tail, jnp.int32(hi - lo))
        outs.append(o[:, : hi - lo])
    # slots: 0 idle (state garbage that must not move), 1 decoding, 2 idle
    junk = jax.random.normal(jax.random.PRNGKey(7), (1,) + s.shape[1:])
    states = jnp.concatenate([junk, s, junk])[None]
    tails = jnp.concatenate([tail[:1] + 1.0, tail, tail[:1] - 1.0])[None]
    for t in range(30, 37):
        x = jnp.concatenate([u[:, t:t + 1]] * 3)
        o, states, tails = decode(m, x, states, tails)
        outs.append(o[1:2])
    got = np.asarray(jnp.concatenate(outs, axis=1))
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(states[0, 0], junk[0])
    np.testing.assert_array_equal(states[0, 2], junk[0])
    # the state and tail behind the whole sequence: one chunk of 37 rows
    st = RecurrentState.init(CFG, 1, jnp.float32)
    _, s_all, t_all = jax.jit(lambda m, *a: m.prefill_at(*a))(
        m, u, st.s[0], st.conv[0])
    np.testing.assert_allclose(states[0, 1], s_all[0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(tails[0, 1], t_all[0], atol=1e-6, rtol=0)


def _draws(s, t, e, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    g, n, lanes = ss.state_layout(e, N)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (s, t, e)) - 1.0)
    dt = dt.at[:, -1].set(0.0)  # a padding row: nothing moves
    x = jax.random.normal(ks[1], (s, t, e))
    b, c = (jax.random.normal(k, (s, t, N)) for k in ks[2:4])
    a = ss.to_layout(-jnp.exp(jax.random.normal(ks[4], (e, N))))
    stack = jax.random.normal(ks[5], (3, s, g, n, lanes))
    return dt, x, b, c, a, stack


@pytest.mark.parametrize("e", [E, 256])
@pytest.mark.parametrize("name,s,t", [
    ("ssm_step", 5, 1),    # decode: S slots, one row each
    ("ssm_scan", 1, 70),   # prefill: one slot, 70 rows (chunks of 32 + pad)
])
def test_kernel_is_the_recurrence(name, s, t, e):
    """Both call shapes of the one Pallas body through the interpreter
    against the ``jax.numpy`` recurrence, in place in a stack of three
    layers whose other two are left to the bit."""
    dt, x, b, c, a, stack = _draws(s, t, e)
    y_k, st_k = ss.scan(dt, x, b, c, a, stack, 1, name=name, interpret=True)
    y_r, st_r = ss.scan(dt, x, b, c, a, stack, 1, name=name)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(st_k, st_r, rtol=1e-6, atol=1e-6)
    for layer in (0, 2):
        np.testing.assert_array_equal(st_k[layer], stack[layer])
    # a row of dt = 0 (the last) left the state as the row before did
    if t > 1:
        _, before = ss.scan(dt[:, :-1], x[:, :-1], b[:, :-1], c[:, :-1], a,
                            stack, 1, name=name, interpret=True)
        np.testing.assert_array_equal(before[1], st_k[1])


def test_state_layout_has_no_padding():
    """``[E, N]`` as ``[E / 128, N, 128]``: a state tile of the chip is N
    sublanes of 128 lanes, whole; at E = 64 one tile of 64 lanes. Channel
    e, state n lies at ``[e // 128, n, e % 128]``."""
    assert ss.state_layout(5120, 16) == (40, 16, 128)
    assert ss.state_layout(E, N) == (1, 16, 64)
    a = jax.random.normal(jax.random.PRNGKey(0), (256, N))
    lay = np.asarray(ss.to_layout(a))
    assert lay.shape == (2, N, 128)
    e, n = np.meshgrid(np.arange(256), np.arange(N), indexing="ij")
    np.testing.assert_array_equal(lay[e // 128, n, e % 128], np.asarray(a))


def test_the_plan_and_the_recurrent_state():
    """Mamba layers are the plan's recurrent ("linear") kind: the pool
    counts the attention layer only, the state its geometry."""
    assert CFG.recurrent_mixer == "mamba"
    assert CFG.layer_plan == (("linear", 0), ("full", 0), ("linear", 1))
    assert CFG.kv_layers == 1 and CFG.linear_layers == 2
    assert CFG.recurrent_geometry == ((1, N, E), (TAPS - 1, E))
    state = jax.eval_shape(lambda: RecurrentState.init(CFG, 3))
    assert state.s.shape == (2, 3, 1, N, E) and state.s.dtype == jnp.float32
    assert state.conv.shape == (2, 3, TAPS - 1, E)
    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), CFG))
    assert shape.lin_blocks.attn.a_log.shape == (2, E, N)
    assert shape.lm_head is None  # tied
    gdn = ModelConfig(
        block_size=64, vocab_size=64, n_layer=2, n_head=2, n_embd=32,
        layer_types=("linear_attention", "full_attention"),
        linear_key_heads=2, linear_value_heads=2, linear_key_dim=8,
        linear_value_dim=16,
    )
    assert gdn.recurrent_mixer == "gated_delta"
    assert gdn.recurrent_geometry == ((2, 8, 16), (3, 2 * 16 + 32))
    with pytest.raises(AssertionError, match="one recurrent kind"):
        ModelConfig(block_size=64, vocab_size=64, n_layer=2, n_head=2,
                    n_embd=32, layer_types=("linear_attention", "mamba"))


def test_parameter_count_at_the_published_widths():
    """``benchmark/configs/jamba2-3b.json`` through ``GPT.init`` by shapes
    alone: 26 Mamba layers of 104,161,472 (41,241,792 in the mixer), 2
    attention layers of 76,682,240, the tied embedding of 167,772,160 and
    the final norm — 3,029,337,472."""
    from benchmark import program

    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        cfg = program.model_config(json.load(f))
    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shape) == 3_029_337_472
    assert count(shape.lin_blocks) == 26 * 104_161_472
    assert count(shape.lin_blocks.attn) == 26 * 41_241_792
    assert count(shape.blocks) == 2 * 76_682_240
    assert cfg.recurrent_geometry == ((40, 16, 128), (3, 5120))

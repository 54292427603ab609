"""Latent attention and the sigmoid-routed expert layer with a shared expert,
layer by layer at a small size (D 64, 4 heads, q rank 24, latent 16, 8
unrotated and 4 rotary lanes a head, values of 8; 8 experts of 16, top-2, one
shared): the absorbed bodies against the published form, the paged kernel's
latent mode (interpreted) against the gather path, and the router's rules."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import (
    ExpertMLP,
    LatentAttention,
    _gather_attend,
)
from midgpt_tpu.models.layers import rope_tables
from midgpt_tpu.ops import paged_attn

CFG = ModelConfig(
    block_size=128, vocab_size=97, n_layer=3, n_head=4, n_embd=64,
    attention="latent", latent_q=24, latent_kv=16, latent_nope=8,
    latent_rope=4, latent_v=8, rope_base=32e6, mlp="experts", dense_layers=1,
    mlp_hidden=48, experts=8, experts_per_token=2, expert_hidden=16,
    expert_scoring="sigmoid", expert_bias=True, expert_scale=2.5,
    shared_experts=1, qk_norm=False, norm_scale=True, norm_eps=1e-6,
)
PS, T = 4, 27  # pages of 4; 26 pooled positions and the token that asks


@pytest.fixture(scope="module")
def attn():
    return LatentAttention.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (1, T, 64), jnp.float32)


def _tables(t=T):
    sin, cos = rope_tables(CFG.rope_dim, t, CFG.rope_base)
    return jnp.asarray(sin, jnp.float32), jnp.asarray(cos, jnp.float32)


def _pooled(attn, x, n, dtype=jnp.float32, perm=None):
    """The first ``n`` tokens' rows in a one-layer pool, page ``perm[i]``
    holding positions ``[i PS, (i + 1) PS)``; and the block table."""
    sin, cos = _tables()
    rows = attn._project(x, sin, cos)[2][0, :n]  # [n, row]
    pages = -(-n // PS)
    perm = np.arange(pages) if perm is None else perm
    pool = jnp.zeros((1, pages + 2, PS, attn.row), dtype)
    padded = jnp.zeros((pages * PS, attn.row)).at[:n].set(rows)
    pool = pool.at[0, perm].set(
        padded.reshape(pages, PS, attn.row).astype(dtype))
    bt = np.full((1, 8), pages + 2, np.int32)
    bt[0, :pages] = perm
    return pool, jnp.asarray(bt)


def test_the_cache_row_is_latent_then_one_rotary_key_then_zeros(attn, x):
    sin, cos = _tables()
    q_nope, q_rope, rows = attn._project(x, sin, cos)
    assert q_nope.shape == (1, 4, T, 8) and q_rope.shape == (1, 4, T, 4)
    assert rows.shape == (1, T, 128) == (1, T, CFG.latent_row)
    assert not np.asarray(rows[..., 20:]).any()
    # position 0 is not turned; the key is the projection's own lanes
    kv = attn.wkv_a(x)
    np.testing.assert_allclose(rows[0, 0, 16:20], kv[0, 0, 16:], rtol=1e-6)
    assert np.abs(np.asarray(rows[0, 5, 16:20] - kv[0, 5, 16:])).max() > 1e-3


def test_absorbed_decode_step_is_the_published_forms_last_row(attn, x):
    """One decode step over 26 pooled rows (pages out of order, the newest
    row in the window's buffer) against the whole-sequence published form:
    the same sum in another order, float32 on both sides."""
    want = attn(x, *_tables())[0, -1]
    n = T - 1
    pool, bt = _pooled(attn, x, n, perm=np.array([3, 0, 6, 1, 5, 2, 4]))
    sin, cos = _tables()
    rk = jnp.zeros((1, 1, 1, 2, attn.row), jnp.float32)
    mask_pool = jnp.where(jnp.arange(8 * PS) < n, 0.0, -jnp.inf)[None]
    mask_rec = jnp.asarray([0.0, -jnp.inf])
    got, rk = attn.decode_paged_at(
        x[:, -1:], pool, bt, rk, 0, jnp.int32(0), mask_pool, mask_rec,
        sin[n][None, None, None], cos[n][None, None, None],
        jnp.asarray([n], jnp.int32),
    )
    np.testing.assert_allclose(got[0, 0], want, atol=2e-6, rtol=1e-5)
    # what the step left in the buffer is the token's pooled row
    np.testing.assert_allclose(
        rk[0, 0, 0, 0], attn._project(x, sin, cos)[2][0, -1], rtol=1e-6)


@pytest.mark.parametrize("start", [0, 8, 19])
def test_prefill_chunk_is_the_published_forms_rows(attn, x, start):
    """A chunk of the rows from ``start`` on against the pooled rows before
    them and themselves: the published form's rows."""
    want = attn(x, *_tables())[0, start:]
    pool, bt = _pooled(attn, x, start) if start else (
        jnp.zeros((1, 3, PS, attn.row)), jnp.full((1, 8), 3, jnp.int32))
    sin, cos = _tables()
    t = T - start
    ii = jnp.arange(t)
    got, rows = attn.prefill_paged_at(
        x[:, start:], pool, bt, 0,
        jnp.where(jnp.arange(8 * PS) < start, 0.0, -jnp.inf),
        jnp.where(ii[None, :] <= ii[:, None], 0.0, -jnp.inf),
        sin[start:], cos[start:],
    )
    np.testing.assert_allclose(got[0], want, atol=2e-6, rtol=1e-5)
    assert rows.shape == (1, 1, t, attn.row)


def test_prefill_takes_the_heads_in_groups_to_the_same_rows(attn, x, monkeypatch):
    """Past ``_LATENT_SCORE_BYTES`` of scores the heads go a group at a
    time: the same rows."""
    from midgpt_tpu.models import gpt

    pool, bt = _pooled(attn, x, 8)
    sin, cos = _tables()
    ii = jnp.arange(T - 8)
    args = (x[:, 8:], pool, bt, 0,
            jnp.where(jnp.arange(8 * PS) < 8, 0.0, -jnp.inf),
            jnp.where(ii[None, :] <= ii[:, None], 0.0, -jnp.inf),
            sin[8:], cos[8:])
    whole = attn.prefill_paged_at(*args)[0]
    monkeypatch.setattr(gpt, "_LATENT_SCORE_BYTES", 1)
    np.testing.assert_allclose(
        attn.prefill_paged_at(*args)[0], whole, atol=1e-6)


def test_the_rotary_key_is_one_a_token(attn, x):
    """Every head reads the same rotary key: a change to the pooled row's
    rotary lanes at one position moves every head's output (and a change to
    the padding lanes behind them moves nothing)."""
    n = T - 1
    pool, bt = _pooled(attn, x, n)
    sin, cos = _tables()
    q_nope, q_rope, rows = attn._project(x[:, -1:], sin[n:], cos[n:])
    q_hat = attn._absorb(q_nope, q_rope)[:, None]  # [1, 1, H, 1, row]
    own = rows[:, None]
    mask = jnp.where(jnp.arange(8 * PS) < n, 0.0, -jnp.inf)

    def heads(pool):
        return np.asarray(_gather_attend(
            q_hat, own, own[..., :16], mask, jnp.zeros((1,)), pool, None,
            None, None, bt, 0, scale_dim=12))[0, 0, :, 0]  # [H, 16]

    base = heads(pool)
    moved = heads(pool.at[0, 1, 2, 16:20].add(3.0))
    assert (np.abs(moved - base).max(axis=-1) > 1e-4).all()
    np.testing.assert_array_equal(heads(pool.at[0, 1, 2, 20:].add(3.0)), base)


def _kernel_case(attn, dtype, lens, seed=0, r=1, window=2):
    """S slots of ragged lengths over one pool of random rows."""
    s, pmax = len(lens), 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(ks[0], (2, s * pmax + 1, PS, attn.row)).astype(dtype)
    pool = pool.at[..., 20:].set(0)
    bt = jax.random.permutation(ks[1], s * pmax).reshape(s, pmax).astype(jnp.int32)
    q = jax.random.normal(ks[2], (s, 1, 4, attn.row)).astype(dtype)
    rows = jax.random.normal(ks[3], (s, 1, window, attn.row)).astype(dtype)
    return q, pool, bt, jnp.asarray(lens, jnp.int32), rows, jnp.int32(r)


def _both_paths(q, pool, bt, lens, rows, r, layer=1):
    got = paged_attn.paged_latent_attention(
        q, pool, bt, lens, rows, r, layer, v_lanes=16, scale_dim=12,
        interpret=True)
    w = bt.shape[1] * PS
    mask_pool = jnp.where(
        jnp.arange(w)[None] < lens[:, None], 0.0, -jnp.inf
    )[:, None, None, None, :]
    mask_rec = jnp.where(jnp.arange(rows.shape[2]) <= r, 0.0, -jnp.inf)
    want = _gather_attend(
        q[:, :, :, None], rows, rows[..., :16], mask_pool, mask_rec, pool,
        None, None, None, bt, layer, scale_dim=12)[:, :, :, 0]
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("band_pages", [None, 2])
def test_kernel_latent_mode_is_the_gather_path(attn, paged_hook, band_pages):
    """The kernel's latent mode, interpreted, against the gather path over
    an f32 pool, rtol 1e-5 (the contract's clause 2): ragged lengths, an
    empty slot, a slot that ends inside a band, one band and four."""
    if band_pages:
        paged_hook("_FORCE_BAND_PAGES", band_pages)
    got, want = _both_paths(*_kernel_case(attn, jnp.float32, [26, 0, 32, 5]))
    assert got.shape == (4, 1, 4, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_kernel_latent_mode_over_a_bf16_pool_is_within_an_ulp(attn, paged_hook):
    paged_hook("_FORCE_BAND_PAGES", 4)
    got, want = _both_paths(*_kernel_case(attn, jnp.bfloat16, [31, 9, 17]))
    # the gather path's f32 result, rounded as the kernel's output is
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
    assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()


def test_kernel_latent_mode_scales_by_the_heads_width_not_the_rows(attn):
    """``scale_dim`` is what the softmax divides by: at the row's width the
    output is another."""
    q, pool, bt, lens, rows, r = _kernel_case(attn, jnp.float32, [26, 12])
    got, _ = _both_paths(q, pool, bt, lens, rows, r)
    other = paged_attn.paged_latent_attention(
        q, pool, bt, lens, rows, r, 1, v_lanes=16, scale_dim=128,
        interpret=True)
    assert np.abs(np.asarray(other) - got).max() > 1e-2


def test_the_cells_geometry_is_supported_and_resident():
    """32 slots' worth of heads against 33,792 positions of 640-lane rows:
    a plan exists at every page size the cell could take, the table is
    resident (41 MB) beside 13 MB of score rows, within the budget."""
    for ps in (16, 32, 64):
        pmax = 33792 // ps
        kw = dict(groups=32, heads=1, latent=True)
        assert paged_attn.supported(pmax, ps, 640, 2, **kw)
        bp = paged_attn.resolved_band_pages(pmax, ps, 640, 2, True)
        assert bp * ps >= 128 and pmax // bp <= paged_attn.MAX_BANDS
        need = paged_attn.vmem_bytes(pmax, ps, 640, 2, **kw)
        assert 33792 * 640 * 2 < need <= paged_attn.VMEM_BUDGET
    # the modes that were there plan as they did
    assert paged_attn.band_pages(128, 16, 128, 2) == 8
    assert paged_attn.band_pages(2112, 16, 640, 2) is None


# -- the router ---------------------------------------------------------------


@pytest.fixture(scope="module")
def mlp():
    return ExpertMLP.init(jax.random.PRNGKey(2), CFG)


@pytest.fixture(scope="module")
def rows_in():
    return jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)


def _routing(mlp, h):
    s = jax.nn.sigmoid(h @ mlp.router.weight)
    _, chosen = jax.lax.top_k(s + mlp.bias, 2)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    return s, chosen, 2.5 * g / g.sum(-1, keepdims=True)


def _by_hand(mlp, h):
    _, chosen, g = _routing(mlp, h)
    out = mlp.shared(h[None])[0]
    for j in range(2):
        w_in, w_out = mlp.w_in[chosen[:, j]], mlp.w_out[chosen[:, j]]
        u = jnp.einsum("nd,ndf->nf", h, w_in)
        y = jnp.einsum("nf,nfd->nd", jax.nn.silu(u[:, :16]) * u[:, 16:], w_out)
        out = out + g[:, j:j + 1] * y
    return out


def test_expert_layer_is_the_equations_by_hand(mlp, rows_in):
    """sigmoid scores, the chosen two's weights renormalised to 2.5, every
    expert a SwiGLU, the shared expert added once."""
    y, _, rows = mlp(rows_in, return_rows=True)
    np.testing.assert_allclose(y, _by_hand(mlp, rows_in), atol=2e-5)
    assert int(rows.sum()) == 2 * 40  # nothing is dropped
    _, _, g = _routing(mlp, rows_in)
    np.testing.assert_allclose(g.sum(-1), 2.5, rtol=1e-6)


def test_the_bias_changes_who_is_chosen_and_never_a_weight(mlp, rows_in):
    big = dataclasses.replace(
        mlp, bias=jnp.zeros((8,)).at[5].set(10.0).at[2].set(-10.0))
    s, chosen, g = _routing(big, rows_in)
    assert (chosen == 5).any(-1).all() and not (chosen == 2).any()
    # the weights are the unbiased scores': expert 5's is its sigmoid over
    # the pair's sum, far from the 10 that chose it
    np.testing.assert_allclose(
        g, 2.5 * jnp.take_along_axis(s, chosen, -1)
        / jnp.take_along_axis(s, chosen, -1).sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(
        big(rows_in)[0], _by_hand(big, rows_in), atol=2e-5)
    assert np.abs(np.asarray(big(rows_in)[0] - mlp(rows_in)[0])).max() > 1e-3


def test_the_shared_expert_is_added_once(mlp, rows_in):
    bare = dataclasses.replace(mlp, shared=None)
    np.testing.assert_allclose(
        mlp(rows_in)[0] - bare(rows_in)[0], mlp.shared(rows_in[None])[0],
        atol=2e-5)


def test_nothing_is_dropped_at_full_skew(mlp, rows_in):
    """Every row on the same two experts: all 80 claims are computed."""
    skew = dataclasses.replace(
        mlp, bias=jnp.full((8,), -10.0).at[jnp.asarray([1, 6])].set(10.0))
    y, _, rows = skew(rows_in, return_rows=True)
    assert list(np.asarray(rows)) == [0, 40, 0, 0, 0, 0, 40, 0]
    np.testing.assert_allclose(y, _by_hand(skew, rows_in), atol=2e-5)


def test_softmax_routing_without_the_rest_is_the_layer_it_was(rows_in):
    """No bias, scale 1, no shared expert, softmax scoring: the traced layer
    has no op of this PR's (what ``serve-sdar-block4`` runs)."""
    plain = ExpertMLP.init(jax.random.PRNGKey(2), dataclasses.replace(
        CFG, expert_scoring="softmax", expert_bias=False, expert_scale=1.0,
        shared_experts=0))
    assert plain.bias is None and plain.shared is None
    text = str(jax.make_jaxpr(lambda m, h: m(h))(plain, rows_in))
    # (a SwiGLU's own logistic is over [claims, F]; the router's would be
    # over [rows, experts])
    assert "f32[40,8] = logistic" not in text and "reduce_max" in text
    sig = str(jax.make_jaxpr(lambda m, h: m(h))(
        ExpertMLP.init(jax.random.PRNGKey(2), CFG), rows_in))
    assert "f32[40,8] = logistic" in sig
    y, _ = plain(rows_in)
    p = jax.nn.softmax(rows_in @ plain.router.weight, axis=-1)
    g, chosen = jax.lax.top_k(p, 2)
    np.testing.assert_allclose((g / g.sum(-1, keepdims=True)).sum(-1), 1.0,
                               rtol=1e-6)
    assert y.shape == rows_in.shape

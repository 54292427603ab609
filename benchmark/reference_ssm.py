"""The plain reference of a model of Mamba-1 and full-attention layers
(``jamba``), ISSUE 42's layer equations in straightforward ``jax.numpy`` and
float32, independent of ``midgpt_tpu``: it imports nothing of the program
and takes nothing the program made. Its weights come from
:mod:`benchmark.weights_ssm` and the seed; its sizes from the configuration
file's published keys (``hidden_size``, ``mamba_*``, ...), and its order of
layers from ``attn_layer_offset`` / ``attn_layer_period``
(``weights_ssm.layer_types``).

Every layer: ``h = x + mixer(RMSNorm_1(x))``, ``y = h + W_down(silu(W_gate
RMSNorm_2(h)) * W_up RMSNorm_2(h))`` (``num_experts`` is 1: a dense SwiGLU in
every layer); a final RMSNorm, then the head tied to the embedding. Every
RMSNorm has a learned scale and eps ``rms_norm_eps``; no bias but the
convolution's and the step size's.

Mamba mixer, E = ``mamba_expand`` D channels, N = ``mamba_d_state``, R =
``mamba_dt_rank``: ``x | z = u W_in``; ``x~ = silu(conv(x) + b_conv)``, a
causal depthwise convolution of ``mamba_d_conv`` taps over time (zero left
pad); ``dt | B | C = x~ W_x``, then ``RMSNorm_dt(dt)``, ``RMSNorm_B(B)``,
``RMSNorm_C(C)`` (Jamba's inner norms); ``Delta = softplus(dt W_dt +
b_dt)``; ``A = -exp(A_log)``; from a zero state ``h_t = exp(Delta_t A) h_{t-1}
+ (Delta_t x~_t) B_t``, ``h`` of ``[E, N]``; ``y_t = h_t C_t + D x~_t``;
``W_out(y * silu(z))``. The recurrence is a ``lax.scan`` over the tokens, one
at a time: no chunks, no carried tail, no cache, no layout.

Attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
of ``hidden_size / num_attention_heads``; no positional encoding, no QK
norm; causal ``softmax(q k^T / sqrt(C)) v``; ``W_o``.

Matrix products run at ``Precision.HIGHEST`` (``benchmark.reference._mm``),
the scan's read ``h C`` among them. ``quant`` rounds both operands of every
matrix product first: how the control computes in a lower precision.
``inner_norms=False`` leaves out Jamba's three inner norms: a stand-in that
has to come out not correct.

Memory: one sequence, one layer at a time; a layer's leaves are widened to
float32 as it is used (one jitted function a kind of layer)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights_ssm
from benchmark.reference import HI, Quant, _mm, _round_to

_FULL = ("wqkv", "wo")
_MAMBA = weights_ssm._MAMBA
_BLOCK = weights_ssm._BLOCK


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mlp_residual(x, mixed, lw, eps, quant):
    h = x + mixed
    u = _rms(h, lw["ln2"], eps)
    u = jax.nn.silu(_mm(u, lw["w_gate"], quant)) * _mm(u, lw["w_up"], quant)
    return h + _mm(u, lw["w_down"], quant)


def _full_layer(x, lw, sizes, quant):
    """``x`` [T, D] through one attention layer."""
    k = weights_ssm.dims(sizes)
    t, h, hkv, c = x.shape[0], k["h"], k["hkv"], k["c"]
    eps = sizes["rms_norm_eps"]
    qkv = _mm(_rms(x, lw["ln1"], eps), lw["wqkv"], quant)
    q = qkv[:, : h * c].reshape(t, h, c)
    kk = jnp.repeat(qkv[:, h * c : (h + hkv) * c].reshape(t, hkv, c),
                    h // hkv, axis=1)
    v = jnp.repeat(qkv[:, (h + hkv) * c :].reshape(t, hkv, c), h // hkv,
                   axis=1)
    s = jnp.einsum("qhc,khc->hqk", _round_to(q, quant), _round_to(kk, quant),
                   precision=HI)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s / math.sqrt(c), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khc->qhc", _round_to(p, quant), _round_to(v, quant),
                   precision=HI)
    return _mlp_residual(x, _mm(o.reshape(t, h * c), lw["wo"], quant), lw,
                         eps, quant)


def selective_scan(delta, x, b, c, a, quant: Quant = None):
    """The recurrence, a token at a time: ``delta``, ``x`` [T, E], ``b``,
    ``c`` [T, N], ``a`` [E, N] -> ``y`` [T, E] (without ``D x``) and the last
    state [E, N]."""

    def step(h, xs):
        delta, x, b, c = xs
        h = jnp.exp(delta[:, None] * a) * h + (delta * x)[:, None] * b[None, :]
        y = jnp.einsum("en,n->e", _round_to(h, quant), _round_to(c, quant),
                       precision=HI)
        return h, y

    h0 = jnp.zeros(a.shape, jnp.float32)
    h, y = jax.lax.scan(step, h0, (delta, x, b, c))
    return y, h


def _mamba_layer(x, lw, sizes, quant, inner_norms):
    """``x`` [T, D] through one Mamba layer."""
    k = weights_ssm.dims(sizes)
    t, e, n, r, taps = x.shape[0], k["e"], k["n"], k["r"], k["taps"]
    eps = sizes["rms_norm_eps"]
    xz = _mm(_rms(x, lw["ln1"], eps), lw["w_in"], quant)
    xs, z = xz[:, :e], xz[:, e:]
    padded = jnp.pad(xs, ((taps - 1, 0), (0, 0)))
    xc = jax.nn.silu(
        sum(lw["conv"][j] * padded[j : j + t] for j in range(taps))
        + lw["conv_bias"])
    p = _mm(xc, lw["w_x"], quant)
    dt, b, c = p[:, :r], p[:, r : r + n], p[:, r + n :]
    if inner_norms:
        dt = _rms(dt, lw["dt_norm"], eps)
        b = _rms(b, lw["b_norm"], eps)
        c = _rms(c, lw["c_norm"], eps)
    delta = jax.nn.softplus(_mm(dt, lw["w_dt"], quant) + lw["dt_bias"])
    y, _ = selective_scan(delta, xc, b, c, -jnp.exp(lw["a_log"]), quant)
    y = (y + lw["d"] * xc) * jax.nn.silu(z)
    return _mlp_residual(x, _mm(y, lw["w_out"], quant), lw, eps, quant)


def make_sequence_logits(sizes, *, quant: Quant = None,
                         inner_norms: bool = True):
    """``f(w, seq [T]) -> logits [T, V]`` float32; ``w`` may be stored in
    bfloat16 (each layer is widened as it is used)."""
    f32 = jnp.float32
    widen = lambda lw: {n: a.astype(f32) for n, a in lw.items()}  # noqa: E731
    full = jax.jit(lambda x, lw: _full_layer(x, widen(lw), sizes, quant))
    mamba = jax.jit(lambda x, lw: _mamba_layer(x, widen(lw), sizes, quant,
                                               inner_norms))

    @jax.jit
    def head(x, ln_f, wte):
        return _mm(_rms(x, ln_f.astype(f32), sizes["rms_norm_eps"]),
                   wte.astype(f32).T, quant)

    @functools.partial(jax.jit, static_argnames=("prefix", "names"))
    def layer_of(w, i, prefix, names):
        return {n: w[prefix + n][i] for n in names}

    def f(w, seq):
        x = jnp.take(w["wte"], seq, axis=0).astype(f32)
        at = {"f_": 0, "m_": 0}
        for kind in weights_ssm.layer_types(sizes):
            p, fn, names = (("m_", mamba, _MAMBA + _BLOCK)
                            if kind == "mamba"
                            else ("f_", full, _FULL + _BLOCK))
            x = fn(x, layer_of(w, at[p], p, names))
            at[p] += 1
        return head(x, w["ln_f"], w["wte"])

    return f

"""The plain reference of a model of full-attention and gated-delta-rule
layers (``olmo_hybrid``), ISSUE 32's layer equations in straightforward
``jax.numpy`` and float32, independent of ``midgpt_tpu``: it imports nothing
of the program and takes nothing the program made. Its weights come from
:mod:`benchmark.weights_hybrid` and the seed.

The block (both kinds; OLMo 2's reordered norm): ``h = x + RMSNorm(mixer(x))``,
``y = h + RMSNorm(MLP(h))``, ``MLP(h) = W_down(silu(W_gate h) * W_up h)``; a
final RMSNorm, then the untied head. Every RMSNorm has a learned scale and eps
``norm_eps``; no bias anywhere.

Full attention: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over the whole
projection, ``v = x Wv``; heads of ``head_width``; no rotary embedding; causal
``softmax(q k^T / sqrt(C)) v``; ``Wo``.

Linear attention (the gated delta rule), per token t and head:
``q~, k~, v~ = x Wq, x Wk, x Wv``; a causal depthwise convolution of
``linear_conv`` taps over time (zero left pad) on each, then SiLU;
``q <- q / |q| / sqrt(dk)``, ``k <- k / |k|`` (eps 1e-6 under the root);
``beta = sigmoid(x Wb)`` (doubled where ``linear_neg_eigval``);
``g = -exp(A_log) softplus(x Wa + dt_bias)``; a state ``S`` of ``[dk, dv]``
from zeros: ``S' = exp(g) S``, ``u = beta (v - S'^T k)``, ``S = S' + k u^T``,
``o = S^T q``; ``y = RMSNorm_dv(o) * silu(x Wg)``, heads side by side, ``Wo``.
The rule is a ``lax.scan`` over the tokens, one at a time: no chunks, no
carried convolution tail, no cache.

Matrix products run at ``Precision.HIGHEST`` (``benchmark.reference._mm``).
``quant`` rounds both operands of every matrix product first, the rule's two
reads of the state among them: how the control computes in a lower precision.

Memory: one sequence, one layer at a time; a layer's leaves are widened to
float32 as it is used (two jitted functions, one a kind of layer), so that at
the benchmark's widths the bfloat16 weights and one layer in float32 are what
is live beside the activations."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import HI, Quant, _mm, _round_to

_FULL = ("wqkv", "wo", "q_norm", "k_norm")
_LINEAR = ("wqkv", "conv", "wg", "wba", "a_log", "dt_bias", "o_norm", "wo")
_BLOCK = ("ln1", "ln2", "w_gate", "w_up", "w_down")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mlp_residual(x, mixed, lw, eps, quant):
    h = x + _rms(mixed, lw["ln1"], eps)
    u = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"], quant)
    return h + _rms(_mm(u, lw["w_down"], quant), lw["ln2"], eps)


def _full_layer(x, lw, sizes, quant):
    """``x`` [T, D] through one full-attention block."""
    t = x.shape[0]
    h, hkv, c = sizes["n_head"], sizes["n_kv_head"], sizes["head_width"]
    eps = sizes["norm_eps"]
    qkv = _mm(x, lw["wqkv"], quant)
    q = _rms(qkv[:, : h * c], lw["q_norm"], eps).reshape(t, h, c)
    k = _rms(qkv[:, h * c : (h + hkv) * c], lw["k_norm"], eps)
    k = jnp.repeat(k.reshape(t, hkv, c), h // hkv, axis=1)
    v = jnp.repeat(qkv[:, (h + hkv) * c :].reshape(t, hkv, c), h // hkv, axis=1)
    s = jnp.einsum("qhc,khc->hqk", _round_to(q, quant), _round_to(k, quant),
                   precision=HI)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s / math.sqrt(c), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khc->qhc", _round_to(p, quant), _round_to(v, quant),
                   precision=HI)
    return _mlp_residual(x, _mm(o.reshape(t, h * c), lw["wo"], quant), lw,
                         eps, quant)


def delta_rule(q, k, v, g, beta, quant: Quant = None):
    """The recurrence, a token at a time: ``q``, ``k`` [T, H, dk], ``v``
    [T, H, dv], ``g``, ``beta`` [T, H] -> ``o`` [T, H, dv] (and the last
    state [H, dk, dv])."""

    def step(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[:, None, None]
        read = jnp.einsum("hk,hkv->hv", _round_to(k, quant),
                          _round_to(s, quant), precision=HI)
        u = beta[:, None] * (v - read)
        s = s + k[:, :, None] * u[:, None, :]
        o = jnp.einsum("hk,hkv->hv", _round_to(q, quant), _round_to(s, quant),
                       precision=HI)
        return s, o

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _linear_layer(x, lw, sizes, quant):
    """``x`` [T, D] through one linear-attention block."""
    t = x.shape[0]
    hk, hv = sizes["linear_key_heads"], sizes["linear_value_heads"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    taps, eps = sizes["linear_conv"], sizes["norm_eps"]
    raw = _mm(x, lw["wqkv"], quant)
    padded = jnp.pad(raw, ((taps - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(lw["conv"][j] * padded[j : j + t] for j in range(taps)))
    q = y[:, : hk * dk].reshape(t, hk, dk)
    k = y[:, hk * dk : 2 * hk * dk].reshape(t, hk, dk)
    v = y[:, 2 * hk * dk :].reshape(t, hv, dv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) / math.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    ba = _mm(x, lw["wba"], quant)
    beta = jax.nn.sigmoid(ba[:, :hv])
    if sizes["linear_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lw["a_log"]) * jax.nn.softplus(ba[:, hv:] + lw["dt_bias"])
    o, _ = delta_rule(q, k, v, g, beta, quant)
    gate = jax.nn.silu(_mm(x, lw["wg"], quant)).reshape(t, hv, dv)
    mixed = _mm((_rms(o, lw["o_norm"], eps) * gate).reshape(t, hv * dv),
                lw["wo"], quant)
    return _mlp_residual(x, mixed, lw, eps, quant)


def make_sequence_logits(sizes, *, quant: Quant = None):
    """``f(w, seq [T]) -> logits [T, V]`` float32; ``w`` may be stored in
    bfloat16 (each layer is widened as it is used)."""
    f32 = jnp.float32
    widen = lambda lw: {n: a.astype(f32) for n, a in lw.items()}  # noqa: E731
    full = jax.jit(lambda x, lw: _full_layer(x, widen(lw), sizes, quant))
    linear = jax.jit(lambda x, lw: _linear_layer(x, widen(lw), sizes, quant))

    @jax.jit
    def head(x, ln_f, lm_head):
        return _mm(_rms(x, ln_f.astype(f32), sizes["norm_eps"]),
                   lm_head.astype(f32), quant)

    @functools.partial(jax.jit, static_argnames=("prefix", "names"))
    def layer_of(w, i, prefix, names):
        return {n: w[prefix + n][i] for n in names}

    def f(w, seq):
        x = jnp.take(w["wte"], seq, axis=0).astype(f32)
        at = {"f_": 0, "l_": 0}
        for kind in sizes["layer_types"]:
            p, fn, names = (("l_", linear, _LINEAR + _BLOCK)
                            if kind == "linear_attention"
                            else ("f_", full, _FULL + _BLOCK))
            x = fn(x, layer_of(w, at[p], p, names))
            at[p] += 1
        return head(x, w["ln_f"], w["lm_head"])

    return f

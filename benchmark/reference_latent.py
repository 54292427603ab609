"""The plain reference of a latent-attention expert model
(``joyai_llm_flash``: DeepSeek-V3's block), ISSUE 34's layer equations in
straightforward ``jax.numpy`` and float32, independent of ``midgpt_tpu``: it
imports nothing of the program and takes nothing the program made. Its weights
come from :mod:`benchmark.weights_latent` and the seed.

The block (pre-norm): ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
a final RMSNorm, then the untied head. Every RMSNorm has a learned scale and
eps ``norm_eps``; no bias in any projection.

Latent attention, in the PUBLISHED form (nothing is absorbed, nothing is
cached): ``cq = RMSNorm(x Wqa)``, a head's ``q = cq Wqb_h`` = q_nope | q_rope;
``[c~ | kr~] = x Wkva``, ``c = RMSNorm(c~)``, ``kr = RoPE(kr~)`` — ONE rotary
key a token, shared by all heads — and ``q_rope <- RoPE(q_rope)``, pairs (2i,
2i+1) at base ``rope_base``; a head's ``[k_nope | v] = c Wkvb_h``; ``o_h =
softmax_causal((q_nope . k_nope + q_rope . kr) / sqrt(nope + rope)) v_h``;
heads side by side through ``Wo``. The rotary angles are formed in float64
(positions run to 33 k, where a float32 angle is off by a thousandth of a
radian).

Feed-forward: the leading ``dense_layers`` layers ``W_down(silu(W_gate h) *
W_up h)``; the others ``s = sigmoid(h Wr)`` over all experts, the
``experts_per_token`` largest of ``s + bias`` chosen (the bias moves the choice
only), ``g = s[chosen] / (sum s[chosen] + 1e-20) * expert_scale``, ``FFN(h) =
sum_e g_e E_e(h) + E_shared(h)``, each ``E`` a SwiGLU of ``expert_hidden``
(the shared one of ``shared_experts`` times that). Dropless: the experts are a
loop over all of them, each over the rows that chose it (gathered to ``cap``
rows, which the caller sets from the fullest expert's count, so that no row is
left out).

Matrix products run at ``Precision.HIGHEST`` (``benchmark.reference._mm``).
``quant`` rounds both operands of every matrix product first: how the control
computes in a lower precision. ``wrong_scale`` divides the scores by the
square root of the cached row's width (``latent_kv + latent_rope``) and not of
the head's: the planted fault an absorbed kernel invites.

Memory: one sequence, one layer at a time, a head at a time and ``ROWS`` query
rows at a time inside it; a layer's leaves are widened to float32 as they are
used, an expert's as its turn comes; the head runs on the rows asked for
only."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HI, Quant, _mm, _round_to

ROWS = 1024  # query rows of one head scored at a time; MLP rows at a time

_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1",
         "ln2")
_DENSE = ("w_gate", "w_up", "w_down")
_EXPERT = ("router", "bias", "w13", "w2", "s_gate", "s_up", "s_down")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, base):
    """``x`` [T, C] at positions 0..T-1; pairs (2i, 2i+1) rotate by ``pos *
    base**(-2i/C)``, the angle formed in float64."""
    t, c = x.shape
    inv = 1.0 / (base ** (np.arange(0, c, 2, dtype=np.float64) / c))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    sin = jnp.asarray(np.repeat(np.sin(ang), 2, axis=-1), jnp.float32)
    cos = jnp.asarray(np.repeat(np.cos(ang), 2, axis=-1), jnp.float32)
    rot = jnp.stack((-x[:, 1::2], x[:, ::2]), axis=-1).reshape(x.shape)
    return x * cos + rot * sin


def _by_rows(fn, x, rows=ROWS):
    """``fn`` over ``x`` [T, ...] a block of rows at a time."""
    t = x.shape[0]
    r = math.gcd(t, rows)
    out = jax.lax.map(fn, x.reshape((t // r, r) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def _swiglu(h, w_gate, w_up, w_down, quant):
    return _by_rows(lambda b: _mm(
        jax.nn.silu(_mm(b, w_gate, quant)) * _mm(b, w_up, quant), w_down,
        quant), h)


def attention(x, lw, sizes, quant: Quant = None, wrong_scale: bool = False):
    """``x`` [T, D] -> ``x + Attn(RMSNorm(x))``."""
    t = x.shape[0]
    h, dq, dc = sizes["n_head"], sizes["latent_q"], sizes["latent_kv"]
    dn, dr, dv = sizes["latent_nope"], sizes["latent_rope"], sizes["latent_v"]
    eps, base = sizes["norm_eps"], sizes["rope_base"]
    width = dc + dr if wrong_scale else dn + dr
    xn = _rms(x, lw["ln1"], eps)
    cq = _rms(_mm(xn, lw["wq_a"], quant), lw["q_norm"], eps)
    kva = _mm(xn, lw["wkv_a"], quant)
    c = _rms(kva[:, :dc], lw["kv_norm"], eps)
    kr = _rope(kva[:, dc:], base)  # one rotary key a token
    r = math.gcd(t, ROWS)

    def head(w):
        wq, wkv = w  # [dq, nope + rope], [dc, nope + v]
        q = _mm(cq, wq, quant)
        q = jnp.concatenate([q[:, :dn], _rope(q[:, dn:], base)], axis=-1)
        kv = _mm(c, wkv, quant)
        k = _round_to(jnp.concatenate([kv[:, :dn], kr], axis=-1), quant)
        v = _round_to(kv[:, dn:], quant)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * r, r, axis=0)
            s = jnp.einsum("qc,kc->qk", _round_to(qb, quant), k, precision=HI)
            seen = jnp.arange(t)[None, :] <= (i * r + jnp.arange(r))[:, None]
            p = jax.nn.softmax(
                jnp.where(seen, s / math.sqrt(width), -jnp.inf), axis=-1)
            return jnp.einsum("qk,kc->qc", _round_to(p, quant), v,
                              precision=HI)

        return jax.lax.map(block, jnp.arange(t // r)).reshape(t, dv)

    o = jax.lax.map(head, (
        jnp.transpose(lw["wq_b"].reshape(dq, h, dn + dr), (1, 0, 2)),
        jnp.transpose(lw["wkv_b"].reshape(dc, h, dn + dv), (1, 0, 2)),
    ))  # [H, T, v]
    o = jnp.transpose(o, (1, 0, 2)).reshape(t, h * dv)
    return x + _mm(o, lw["wo"], quant)


def route(hn, lw, sizes, quant: Quant = None):
    """The router on normed rows ``hn`` [T, D]: the chosen experts [T, k] and
    their weights [T, k]."""
    s = jax.nn.sigmoid(_mm(hn, lw["router"], quant))  # [T, E]
    _, chosen = jax.lax.top_k(s + lw["bias"], sizes["experts_per_token"])
    g = jnp.take_along_axis(s, chosen, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return chosen, g * sizes["expert_scale"]


def experts(hn, chosen, g, w13, w2, cap: int, quant: Quant = None):
    """``sum_e g_e E_e(h)`` [T, D]: a loop over all the experts, each over
    the (at most ``cap``) rows that chose it. ``w13`` [E, D, 2F] and ``w2``
    [E, F, D] may be stored in bfloat16: an expert is widened at its turn."""
    t, d = hn.shape
    f = w2.shape[1]
    f32 = jnp.float32

    def one(out, ew):
        e, w13_e, w2_e = ew
        mine = chosen == e  # [T, k]
        rows = jnp.nonzero(jnp.any(mine, axis=-1), size=cap, fill_value=t)[0]
        gate = jnp.take(jnp.sum(jnp.where(mine, g, 0.0), axis=-1), rows,
                        mode="fill", fill_value=0.0)
        xe = jnp.take(hn, rows, axis=0, mode="fill", fill_value=0.0)
        u = _mm(xe, w13_e.astype(f32), quant)
        y = _mm(jax.nn.silu(u[:, :f]) * u[:, f:], w2_e.astype(f32), quant)
        return out.at[rows].add(y * gate[:, None], mode="drop"), None

    out, _ = jax.lax.scan(
        one, jnp.zeros((t, d), f32),
        (jnp.arange(w2.shape[0]), w13, w2))
    return out


def make_sequence_logits(sizes, *, quant: Quant = None,
                         wrong_scale: bool = False):
    """``f(w, seq [T], at [n]) -> logits [n, V]`` float32: one full forward
    over ``seq`` (causal: what follows the rows asked for may be padding),
    the head on the rows ``at`` only. ``w`` may be stored in bfloat16."""
    f32 = jnp.float32
    eps = sizes["norm_eps"]
    widen = lambda lw: {n: a.astype(f32) for n, a in lw.items()}  # noqa: E731

    attn = jax.jit(lambda x, lw: attention(
        x, widen(lw), sizes, quant, wrong_scale))

    @jax.jit
    def dense(x, lw):
        lw = widen(lw)
        return x + _swiglu(_rms(x, lw["ln2"], eps), lw["w_gate"], lw["w_up"],
                           lw["w_down"], quant)

    @jax.jit
    def routed(x, lw):
        lw = widen(lw)
        hn = _rms(x, lw["ln2"], eps)
        chosen, g = route(hn, lw, sizes, quant)
        fullest = jnp.max(jnp.zeros((sizes["experts"],), jnp.int32).at[
            chosen.reshape(-1)].add(1))
        return hn, chosen, g, fullest

    @functools.partial(jax.jit, static_argnames=("cap",))
    def sparse(x, hn, chosen, g, lw, w13, w2, cap):
        lw = widen(lw)
        return (x + experts(hn, chosen, g, w13, w2, cap, quant)
                + _swiglu(hn, lw["s_gate"], lw["s_up"], lw["s_down"], quant))

    @jax.jit
    def head(x, at, ln_f, lm_head):
        return _mm(_rms(jnp.take(x, at, axis=0), ln_f.astype(f32), eps),
                   lm_head.astype(f32), quant)

    @functools.partial(jax.jit, static_argnames=("prefix", "names"))
    def layer_of(w, i, prefix, names):
        return {n: w[prefix + n][i] for n in names}

    def f(w, seq, at):
        x = jnp.take(w["wte"], seq, axis=0).astype(f32)
        t = x.shape[0]
        for n in range(sizes["n_layer"]):
            nd = sizes["dense_layers"]
            p, i = ("d_", n) if n < nd else ("e_", n - nd)
            x = attn(x, layer_of(w, i, p, _ATTN))
            if p == "d_":
                x = dense(x, layer_of(w, i, p, ("ln2",) + _DENSE))
                continue
            small = tuple(a for a in _EXPERT if a not in ("w13", "w2"))
            lw = layer_of(w, i, p, ("ln2",) + small)
            hn, chosen, g, fullest = routed(x, lw)
            # the fullest expert's rows, rounded up so that few shapes compile
            cap = min(t, 256 * -(-int(fullest) // 256))
            x = sparse(x, hn, chosen, g, lw, w["e_w13"][i], w["e_w2"][i], cap)
        return head(x, at, w["ln_f"], w["lm_head"])

    return f

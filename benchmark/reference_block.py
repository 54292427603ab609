"""The plain reference of the block-diffusion expert model (``sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): its forward and its
generation loop in straightforward ``jax.numpy`` and float32, independent of
``midgpt_tpu``. It imports nothing of the program and takes nothing the
program made: its weights come from :mod:`benchmark.weights_block` and the
seed. No kernel, no cache, no batching.

The layer (published config keys in backticks), ``x`` [T, D]:

- ``a = RMSNorm(x)`` (``rms_norm_eps``, learned scale); ``q, k, v = a Wq,
  a Wk, a Wv`` (no bias; ``num_attention_heads`` x ``head_dim`` queries,
  ``num_key_value_heads`` x ``head_dim`` keys and values: the head width is
  its own number, H C != D); per head ``q, k <- RMSNorm_C(q), RMSNorm_C(k)``
  (learned scale over the head width); RoPE by halves (``rotate_half``:
  ``[x1, x2] -> [-x2, x1]``), ``rope_theta``.
- ``s_ij = q_i k_j / sqrt(C) + M_ij``, softmax over j, H / Hkv query heads
  share a KV head (query head h reads KV head h // (H / Hkv));
  ``x <- x + concat_heads(P v) Wo``.
- ``h = RMSNorm(x)``; ``p = softmax(h Wr)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest are chosen; ``g_e = p_e / sum(chosen p)``
  (``norm_topk_prob``); ``x <- x + sum_e g_e (silu(h W1_e) * (h W3_e)) W2_e``:
  here EVERY expert is computed for every row, one at a time, and weighted
  by a gate that is zero where the expert was not chosen. No capacity, no
  dropped token.
- final RMSNorm, untied head.

The mask ``M`` (block length B): position i sees j iff ``j // B <= i // B``.

Generation (the published ``block_diffusion_generate``), with three
departures, none of which changes a result: (1) the set of revealed
positions is kept explicitly and not found by comparing ids with the mask
id, since with random weights the argmax IS the mask id once in V tokens
(with trained weights the two agree); (2) there is no cache, so the commit
pass that stores a block's K/V has nothing to do: every forward recomputes
the earlier blocks from their final tokens, which is what the stored K/V
are; (3) every forward runs at one padded length, the pad behind the mask.

Matrix products run at ``Precision.HIGHEST``; ``quant`` rounds both operands
of every product first (:mod:`benchmark.reference`), which is how the
control computes in a lower precision."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HI, Quant, _mm, _round_to

_LAYER_LEAVES = ("wqkv", "wo", "q_norm", "k_norm", "ln1", "ln2", "router",
                 "w13", "w2")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_half(x, pos, theta):
    """x: [H, T, C]; the pairs (i, i + C/2) rotate by pos * theta**(-2i/C)."""
    c = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, c, 2, dtype=jnp.float32) / c))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [T, C/2]
    sin = jnp.concatenate((jnp.sin(ang), jnp.sin(ang)), axis=-1)
    cos = jnp.concatenate((jnp.cos(ang), jnp.cos(ang)), axis=-1)
    x1, x2 = x[..., : c // 2], x[..., c // 2:]
    return x * cos + jnp.concatenate((-x2, x1), axis=-1) * sin


def _experts(h, lw, top_k: int, renorm: bool, quant: Quant):
    """Every expert on every row, weighted by its gate (zero where it was
    not chosen)."""
    p = jax.nn.softmax(_mm(h, lw["router"], quant), axis=-1)  # [T, E]
    topv, topi = jax.lax.top_k(p, top_k)
    if renorm:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    e = p.shape[-1]
    gates = jnp.sum(
        jax.nn.one_hot(topi, e, dtype=jnp.float32) * topv[..., None], axis=1)
    f = lw["w2"].shape[-2]

    def one(acc, xs):
        w13, w2, g = xs
        u = _mm(h, w13.astype(jnp.float32), quant)
        y = _mm(jax.nn.silu(u[:, :f]) * u[:, f:], w2.astype(jnp.float32),
                quant)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (lw["w13"], lw["w2"], gates.T))
    return out


def _layer(x, lw, pos, mask, sizes, quant):
    t, d = x.shape
    nh, hkv, c = sizes["n_head"], sizes["n_kv_head"], sizes["head_width"]
    eps, theta = float(sizes["norm_eps"]), float(sizes["rope_base"])
    qkv = _mm(_rms(x, lw["ln1"], eps), lw["wqkv"], quant)
    q = qkv[:, : nh * c].reshape(t, nh, c)
    k = qkv[:, nh * c: (nh + hkv) * c].reshape(t, hkv, c)
    v = qkv[:, (nh + hkv) * c:].reshape(t, hkv, c)
    q = _rms(q, lw["q_norm"], 1e-6)
    k = _rms(k, lw["k_norm"], 1e-6)
    q, k, v = (jnp.transpose(a, (1, 0, 2)) for a in (q, k, v))
    q, k = _rope_half(q, pos, theta), _rope_half(k, pos, theta)
    g = nh // hkv
    kk, vv = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)  # h -> h // g
    s = jnp.einsum("hqc,hkc->hqk", _round_to(q, quant), _round_to(kk, quant),
                   precision=HI)
    s = jnp.where(mask[None], s / math.sqrt(c), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,hkc->hqc", _round_to(p, quant), _round_to(vv, quant),
                   precision=HI)
    x = x + _mm(jnp.transpose(o, (1, 0, 2)).reshape(t, nh * c), lw["wo"],
                quant)
    y = _experts(_rms(x, lw["ln2"], eps), lw, int(sizes["experts_per_token"]),
                 bool(sizes.get("expert_renorm", True)), quant)
    return x + y, k, v


def hidden(w, tokens, pos, mask, sizes, *, quant: Quant = None):
    """Hidden states before the final norm [T, D], and every layer's keys
    (normed, rotated) and values [L, Hkv, T, C], for ``tokens`` [T] at
    positions ``pos`` [T] under the boolean ``mask`` [T, T] (row sees
    column)."""
    x = jnp.take(w["wte"].astype(jnp.float32), tokens, axis=0)

    def layer(x, lw):
        small = {k: v.astype(jnp.float32) for k, v in lw.items()
                 if k not in ("w13", "w2")}  # an expert is widened when used
        x, k, v = _layer(x, {**lw, **small}, pos, mask, sizes, quant)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, {k: w[k] for k in _LAYER_LEAVES})
    return x, ks, vs


def logits_of(w, h, sizes, quant: Quant = None):
    return _mm(_rms(h, w["ln_f"].astype(jnp.float32), float(sizes["norm_eps"])),
               w["lm_head"].astype(jnp.float32), quant)


def block_mask(t: int, block: int, causal_inside: bool = False) -> np.ndarray:
    """M: row i sees column j iff ``j // block <= i // block`` (a model
    that is causal inside the block — a planted fault — iff ``j <= i``)."""
    i = np.arange(t)
    if causal_inside:
        return i[None, :] <= i[:, None]
    return (i[None, :] // block) <= (i[:, None] // block)


def make_forward(sizes, *, quant: Quant = None):
    """``f(w, tokens [T], pos [T], mask [T, T]) -> (logits [T, V], ks, vs)``,
    jitted."""

    def f(w, tokens, pos, mask):
        h, ks, vs = hidden(w, tokens, pos, mask, sizes, quant=quant)
        return logits_of(w, h, sizes, quant), ks, vs

    return jax.jit(f)


def confidence(logits):
    """Per row: the greedy pick, its log-confidence (log softmax at the
    pick) and its logit."""
    top = jnp.max(logits, axis=-1)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.argmax(logits, axis=-1), top - logz, top


def generate(w, prompt, n_new: int, sizes, *, quant: Quant = None,
             causal_inside: bool = False, forward=None):
    """The published loop at temperature 0 with ``low_confidence_static``
    reveal: returns the first ``n_new`` generated tokens, per token the
    denoising step at which it was revealed, and the log of what happened:
    one entry ``(block, step, logits [B, V], masked [B], chosen [B])`` a
    denoising forward."""
    b, steps = int(sizes["block_len"]), int(sizes["block_steps"])
    mask_id = int(sizes["mask_token"])
    prompt = np.asarray(prompt, np.int32)
    p = len(prompt)
    total = -(-(p + n_new) // b) * b
    fwd = forward or make_forward(sizes, quant=quant)
    mask = jnp.asarray(block_mask(total, b, causal_inside))
    pos = jnp.arange(total)
    x = np.full((total,), mask_id, np.int32)
    x[:p] = prompt
    revealed = np.zeros((total,), bool)
    revealed[:p] = True
    step_of = np.full((total,), -1, np.int32)
    log = []
    for blk in range(p // b, total // b):
        lo, hi = blk * b, (blk + 1) * b
        for step in range(steps + 1):
            masked = ~revealed[lo:hi]
            if not masked.any():
                break  # the commit pass: nothing to store here
            # positions behind the block are pad: no row in or before the
            # block sees them
            logits = np.asarray(fwd(w, jnp.asarray(x), pos, mask)[0][lo:hi])
            pick, conf, _ = (np.asarray(a) for a in confidence(logits))
            order = np.argsort(-np.where(masked, conf, -np.inf), kind="stable")
            chosen = np.zeros((b,), bool)
            chosen[order[: min(b // steps, int(masked.sum()))]] = True
            log.append((blk, step, logits, masked.copy(), chosen))
            x[lo:hi] = np.where(chosen, pick, x[lo:hi])
            revealed[lo:hi] |= chosen
            step_of[lo:hi] = np.where(chosen, step, step_of[lo:hi])
    return x[p:p + n_new], step_of[p:p + n_new], log


# ---------------------------------------------------------------------------
# Replaying what a server did: every (block, step) state from the final
# tokens and the step at which each was revealed
# ---------------------------------------------------------------------------


def replay_inputs(seq, step_of, block: int, step: int, mask_id: int, *,
                  causal_inside: bool = False, context: str = "final"):
    """The state of every block at denoising step ``step``, all in one
    forward: ``[noisy ; clean]`` of length 2 T with the block-diffusion
    training mask. The noisy half holds each block as it stood before its
    forward of that step (a position is visible iff revealed at an earlier
    step; ``step_of`` < 0 marks prompt positions, always visible); the clean
    half holds the final tokens. A noisy row sees its own noisy block and
    the clean blocks before it; a clean row sees the clean blocks up to its
    own. ``context="last_state"`` plants a fault: the clean half holds each
    block as it stood at its LAST denoising forward (one position still
    masked), which is the K/V a server would keep if it stored them from
    the masked pass and not from the commit pass."""
    seq, step_of = np.asarray(seq, np.int32), np.asarray(step_of, np.int32)
    t = len(seq)
    assert t % block == 0, (t, block)
    noisy = np.where(step_of >= step, mask_id, seq)
    clean = seq
    if context == "last_state":
        last = step_of.reshape(-1, block).max(axis=1).repeat(block)
        clean = np.where((step_of >= 0) & (step_of == last), mask_id, seq)
    bi = np.arange(t) // block
    ii = np.arange(t)
    inside = (ii[None, :] <= ii[:, None]) if causal_inside else True
    same = (bi[None, :] == bi[:, None]) & inside
    before = bi[None, :] < bi[:, None]
    mask = np.block([[same, before], [np.zeros((t, t), bool), before | same]])
    tokens = np.concatenate([noisy, clean]).astype(np.int32)
    pos = np.concatenate([ii, ii]).astype(np.int32)
    return tokens, pos, mask


def make_replay(sizes, *, quant: Quant = None):
    """``f(w, tokens [2T], pos, mask, served [T]) -> (pick, logconf, top,
    served_logit)``, each [T], for the noisy half's rows."""

    def f(w, tokens, pos, mask, served):
        t = served.shape[0]
        h, _, _ = hidden(w, tokens, pos, mask, sizes, quant=quant)
        logits = logits_of(w, h[:t], sizes, quant)
        pick, logconf, top = confidence(logits)
        got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return pick, logconf, top, got

    return jax.jit(f)


def judge(served_steps, checked, readings, block: int, n_reveal: int,
          chooser=None):
    """The two gaps of every reveal, from the reference's ``readings``: a
    step index -> one ``make_replay`` result, as numpy. ``served_steps``
    [T]: the step at which the server revealed each position (< 0: a prompt
    position); ``checked`` [T] bool: positions whose reveal is judged.
    ``chooser`` puts another model in the server's place: a step index ->
    its confidence [T] in each state, the positions it reveals being the
    masked ones it is surest of (its tokens are then what the readings'
    ``served`` held).

    - ``logit_gap``: how far the revealed token's logit lies below the
      reference's best at that position in that state;
    - ``conf_gap``: reference log-confidence of its ``n_reveal``-th surest
      still-masked position of the block, minus that of the position
      revealed (at least 0)."""
    steps = np.asarray(served_steps)
    logit_gap, conf_gap = [], []
    for step, (_, logconf, top, got) in readings.items():
        for lo in range(0, len(steps), block):
            sl = slice(lo, lo + block)
            masked = steps[sl] >= step
            if not masked.any():
                continue
            n = min(n_reveal, int(masked.sum()))
            bar = np.sort(np.where(masked, logconf[sl], -np.inf))[::-1][n - 1]
            if chooser is None:
                revealed = np.nonzero(steps[sl] == step)[0]
            else:
                revealed = np.argsort(
                    -np.where(masked, chooser[step][sl], -np.inf),
                    kind="stable")[:n]
            for i in revealed:
                if checked[lo + i]:
                    logit_gap.append(float(top[lo + i] - got[lo + i]))
                    conf_gap.append(max(0.0, float(bar - logconf[lo + i])))
    return logit_gap, conf_gap

"""The gated delta rule (Gated DeltaNet), the recurrence of a linear-attention
layer. Per head a state ``S`` of ``[dk, dv]`` in float32, and per token

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t

with ``g_t <= 0`` the log of the decay and ``beta_t`` in (0, 2). ``q`` and
``k`` arrive normalised (the mixer's business, models.gpt.GatedDeltaNet).
A token with ``g = 0`` and ``beta = 0`` leaves the state as it was, to the
bit: that is how a caller masks padding and idle slots.

Two forms of the one rule:

- :func:`chunked` — a whole sequence (or a prefill chunk that takes and
  returns state) in chunks of 64 tokens, the WY / UT-transform form: inside
  a chunk the ``u`` of all tokens come out of one unit-lower-triangular
  solve, and only the state crosses chunks (a ``lax.scan``). Plain
  ``jax.numpy``: the Gram products take the operands as they come (bf16 on
  the serving path) and accumulate in float32; decays, the solve and the
  state are float32.
- :func:`step` — one token for S slots against the slots' states, the decode
  form. On a TPU at whole-tile shapes it is one Pallas call that reads a
  slot's states once and writes them once, in place (the state is aliased
  input to output, and the caller hands over the whole ``[L, S, H, dk, dv]``
  stack with the layer as an index: a slice of it would be a copy);
  elsewhere the ``jax.numpy`` body (the pattern of ops/grouped.py: the
  backend and the shapes pick, there is no knob). Its events in a device
  trace are ``%gdn_step.N``.

No backward kernel: :func:`chunked` differentiates as any ``jax.numpy``
code does; nothing trains through :func:`step`."""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.utils.platform import is_tpu_backend

Array = jax.Array

CHUNK = 64
# the step kernel holds a slot's states twice over (in and out), each
# double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# the recurrence, token by token (the oracle of both forms)
# ---------------------------------------------------------------------------


def step_reference(q, k, v, g, beta, state):
    """One token: ``q``, ``k`` ``[S, H, dk]``, ``v`` ``[S, H, dv]``, ``g``,
    ``beta`` ``[S, H]``, ``state`` ``[S, H, dk, dv]`` float32. Returns
    ``(o [S, H, dv] float32, state)``. Broadcast-multiply and reduce in
    float32: a ``[1, dk] x [dk, dv]`` product a slot-head is a matvec."""
    f32 = jnp.float32
    kf, qf = k.astype(f32)[..., None], q.astype(f32)[..., None]
    decayed = state * jnp.exp(g.astype(f32))[..., None, None]
    u = beta.astype(f32)[..., None] * (
        v.astype(f32) - jnp.sum(decayed * kf, axis=-2)
    )
    new = decayed + kf * u[..., None, :]
    return jnp.sum(new * qf, axis=-2), new


def recurrent(q, k, v, g, beta, state):
    """The rule over a sequence, one token at a time (tests' oracle):
    ``q``, ``k`` ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``, ``g``, ``beta``
    ``[B, T, H]``, ``state`` ``[B, H, dk, dv]``."""

    def body(s, xs):
        o, s = step_reference(*xs, s)
        return s, o

    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta))
    state, o = jax.lax.scan(body, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


# ---------------------------------------------------------------------------
# chunked
# ---------------------------------------------------------------------------


def chunked(
    q: Array,  # [B, T, H, dk]
    k: Array,  # [B, T, H, dk]
    v: Array,  # [B, T, H, dv]
    g: Array,  # [B, T, H] float32, <= 0
    beta: Array,  # [B, T, H] float32
    state: Array,  # [B, H, dk, dv] float32
    chunk: int = CHUNK,
) -> tp.Tuple[Array, Array]:
    """``(o [B, T, H, dv] float32, state)``. ``T`` need be no multiple of
    ``chunk``: the tail is padded with tokens that change nothing."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    n = (t + pad) // c

    def split(a):  # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v = split(q), split(k), split(v)
    g, beta = split(g.astype(f32)), split(beta.astype(f32))  # [N, B, H, C]
    with jax.named_scope("gdn_chunk"):
        cum = jnp.cumsum(g, axis=-1)  # log decay from the chunk's start
        # decay from token i to token t >= i; masked before the exp, which
        # would overflow above the diagonal
        low = jnp.tril(jnp.ones((c, c), bool))
        rel = jnp.exp(jnp.where(
            low, cum[..., :, None] - cum[..., None, :], -jnp.inf
        ))  # [N, B, H, C, C]
        kk = jnp.einsum(
            "...tk,...ik->...ti", k, k, preferred_element_type=f32
        )
        qk = jnp.einsum(
            "...tk,...ik->...ti", q, k, preferred_element_type=f32
        ) * rel
        # (I + diag(beta) A) U = diag(beta) (V - diag(e^cum) K S0), A the
        # strictly lower part of kk * rel: solved once for both right-hand
        # sides, u = w_v - w_k S0
        a = beta[..., None] * jnp.where(jnp.tril(low, -1), kk * rel, 0.0)
        rhs = beta[..., None] * jnp.concatenate(
            [v.astype(f32), k.astype(f32) * jnp.exp(cum)[..., None]], axis=-1
        )
        w = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True
        )
        w_v, w_k = w[..., : v.shape[-1]], w[..., v.shape[-1]:]
        q_in = q.astype(f32) * jnp.exp(cum)[..., None]
        # what each token still adds to the state at the chunk's end
        k_out = k.astype(f32) * jnp.exp(cum[..., -1:] - cum)[..., None]
        total = jnp.exp(cum[..., -1])[..., None, None]  # [N, B, H, 1, 1]

        def body(s, xs):
            w_v, w_k, q_in, qk, k_out, total = xs
            u = w_v - jnp.einsum("...tk,...kv->...tv", w_k, s)
            o = (jnp.einsum("...tk,...kv->...tv", q_in, s)
                 + jnp.einsum("...ti,...iv->...tv", qk, u))
            s = s * total + jnp.einsum("...tk,...tv->...kv", k_out, u)
            return s, o

        state, o = jax.lax.scan(
            body, state.astype(f32), (w_v, w_k, q_in, qk, k_out, total)
        )
    # [N, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        b, n * c, h, v.shape[-1]
    )
    return o[:, :t], state


# ---------------------------------------------------------------------------
# step
# ---------------------------------------------------------------------------


def kernel_shapes_ok(h: int, dk: int, dv: int) -> bool:
    """Whole tiles for the step kernel: a head's state is ``dk`` sublanes
    of ``dv`` lanes (``dv`` = 192 pads to 256 lanes: the kernel takes it,
    a quarter of the tile is air), and a slot's states fit the kernel's
    memory twice over, double-buffered."""
    lanes = -(-dv // 128) * 128
    return (
        dk % 8 == 0 and dv % 64 == 0
        and 4 * h * dk * lanes * 4 <= _VMEM_LIMIT // 2
    )


def _step_kernel(alpha_ref, beta_ref, kt_ref, qt_ref, v_ref, s_ref,
                 o_ref, s_out_ref, *, heads: int):
    """One slot (a grid step), its heads in turn. ``kt`` and ``qt`` are
    ``[dk, H]``: a head's k is a column, the state's sublanes; v, u and o
    are rows, its lanes. Both contractions are broadcast-multiplies with a
    reduction over sublanes, the float32 of :func:`step_reference`."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    i = pl.program_id(0)
    kt = kt_ref[...].astype(f32)  # [dk, H]
    qt = qt_ref[...].astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, kt.shape, 1)
    for h in range(heads):
        # a head's column, by a one-hot reduction over the lanes (a lane
        # slice one wide is no aligned load)
        pick = lane == h
        kcol = jnp.sum(jnp.where(pick, kt, 0.0), axis=1, keepdims=True)
        qcol = jnp.sum(jnp.where(pick, qt, 0.0), axis=1, keepdims=True)
        decayed = s_ref[h] * alpha_ref[i, h]  # [dk, dv]
        u = beta_ref[i, h] * (
            v_ref[pl.ds(h, 1), :].astype(f32)
            - jnp.sum(decayed * kcol, axis=0, keepdims=True)
        )  # [1, dv]
        new = decayed + kcol * u
        s_out_ref[h] = new
        o_ref[pl.ds(h, 1), :] = jnp.sum(
            new * qcol, axis=0, keepdims=True
        ).astype(o_ref.dtype)


def _step_call(q, k, v, g, beta, stack, *, layer: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    s, h, dk = q.shape
    dv = v.shape[-1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    per_slot = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None,) + shape, lambda i: (i,) + (0,) * len(shape)
    )
    state_spec = pl.BlockSpec(
        (None, None, h, dk, dv), lambda i: (layer, i, 0, 0, 0)
    )
    o, stack = pl.pallas_call(
        functools.partial(_step_kernel, heads=h),
        grid=(s,),
        in_specs=[
            smem, smem, per_slot(dk, h), per_slot(dk, h), per_slot(h, dv),
            state_spec,
        ],
        out_specs=[per_slot(h, dv), state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((s, h, dv), f32),
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        jnp.exp(g.astype(f32)), beta.astype(f32),
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2), v, stack,
    )
    return o, stack


# jitted under the name a device trace calls the kernel's events by (the
# innermost scope round the call names them: ops.paged_attn._jitted)
def gdn_step(*args, **kwargs):
    return _step_call(*args, **kwargs)


_STEP_CALL = jax.jit(gdn_step, static_argnames=("layer", "interpret"))


def step(
    q: Array,  # [S, H, dk]
    k: Array,  # [S, H, dk]
    v: Array,  # [S, H, dv]
    g: Array,  # [S, H] float32
    beta: Array,  # [S, H] float32
    stack: Array,  # [L, S, H, dk, dv] float32: every linear layer's states
    layer: int,  # STATIC: which of them this call reads and writes
    interpret: bool = False,  # a test's choice, never the program's
) -> tp.Tuple[Array, Array]:
    """One token for S slots: ``(o [S, H, dv] float32, stack)``."""
    _, _, h, dk, dv = stack.shape
    with jax.named_scope("gdn_step"):
        if kernel_shapes_ok(h, dk, dv) and (interpret or is_tpu_backend()):
            return _STEP_CALL(
                q, k, v, g, beta, stack, layer=layer, interpret=interpret
            )
        o, new = step_reference(q, k, v, g, beta, stack[layer])
        return o, stack.at[layer].set(new)

"""The selective scan of a Mamba-1 layer (models.gpt.Mamba). Per channel e
and state index n a float32 state ``h[e, n]``, and per token

    h_t = exp(dt_t[e] A[e, n]) h_{t-1} + dt_t[e] x_t[e] B_t[n];
    y_t[e] = sum_n h_t[e, n] C_t[n]

with ``dt > 0`` the step size, ``A < 0``. A token with ``dt = 0`` leaves the
state as it was, to the bit (``exp(0) = 1``, and nothing is added): that is
how a caller masks padding and idle slots.

The state of one slot and layer is ``[E, N]`` laid as ``[E / L, N, L]``: E
in lane tiles of ``L`` = 128 channels, each tile holding its channels' N
states on sublanes, so that a tile of the chip has no padding (``[E, N]``
would pad its 16 lanes to 128, eight times the bytes). ``A`` comes in the
same layout; the rows' ``dt`` and ``x`` as ``[E / L, L]``.

One Pallas body, two call shapes: S slots of T rows each, a grid over the
slots and over the rows in chunks. A slot's state is read into the kernel's
memory once, stepped over the call's rows, and written once, in place in the
``[Ll, S, E / L, N, L]`` stack (aliased input to output; the caller hands
over the whole stack with the layer as an index: a slice of it would be a
copy). :func:`scan` is the entry point, jitted under two names, so that a
device trace names the calls by use:

- ``ssm_step`` — the decode step: S slots, one row each, every recurrent
  layer of every step of the decode window; events ``%ssm_step.N``;
- ``ssm_scan`` — a prefill chunk: one slot, the chunk's T rows; events
  ``%ssm_scan.N``.

Off the TPU (and on it at shapes the kernel does not take) the body is
:func:`scan_reference`, the recurrence a token at a time in ``jax.numpy``;
a test runs the kernel itself by ``interpret=True``. No ``jax.numpy`` form
materialises a state a token (``[T, E, N]``: 84 MB a layer at T = 256 and
the published widths). No backward: nothing trains through it."""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.utils.platform import is_tpu_backend

Array = jax.Array

# rows of a chunk of the grid: a slot's rows are walked in chunks of this
# many, the state staying in the kernel's memory between them
ROWS = 32
# lane tiles of the state a kernel walks the rows with at once
TILES = 8
_VMEM_LIMIT = 32 * 1024 * 1024


def state_layout(e: int, n: int) -> tp.Tuple[int, int, int]:
    """``[E, N]`` as the stack holds it: ``(E / L, N, L)``."""
    lanes = 128 if e % 128 == 0 else e
    return e // lanes, n, lanes


def to_layout(a: Array) -> Array:
    """An ``[E, N]`` array (``A``) in the state's layout ``[E / L, N, L]``."""
    e, n = a.shape
    g, _, lanes = state_layout(e, n)
    return jnp.transpose(a.reshape(g, lanes, n), (0, 2, 1))


# ---------------------------------------------------------------------------
# the recurrence, token by token (the body off the TPU, and the oracle)
# ---------------------------------------------------------------------------


def scan_reference(dt, x, b, c, a, state):
    """``dt``, ``x`` ``[S, T, G, L]``, ``b``, ``c`` ``[S, T, N]``, ``a``
    ``[G, N, L]``, ``state`` ``[S, G, N, L]``, all float32. Returns ``(y [S,
    T, G, L], state)``."""

    def step(h, xs):
        dt, x, b, c = xs  # [S, G, L] x 2, [S, N] x 2
        h = (jnp.exp(dt[:, :, None, :] * a) * h
             + (dt * x)[:, :, None, :] * b[:, None, :, None])
        return h, jnp.sum(h * c[:, None, :, None], axis=2)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (dt, x, b, c))
    state, y = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def kernel_shapes_ok(g: int, n: int, lanes: int) -> bool:
    """Whole tiles: a state tile is N sublanes of 128 lanes, a row's ``dt``
    and ``x`` ``G`` sublanes of them."""
    return lanes == 128 and n % 8 == 0 and g >= 1


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, s_ref, y_ref, s_out_ref,
                 *, rows: int):
    """One slot's chunk of ``rows`` rows (a grid step). The state stays in
    ``s_out_ref`` from the slot's first chunk to its last. The state's lane
    tiles are taken ``TILES`` at a time, their ``[N, L]`` states in
    registers while the rows are walked: a row's update of one tile waits on
    the row before (an exp in the chain), and that many independent chains
    keep the units busy meanwhile. ``dt``, ``x`` and ``y`` are ``[rows, G,
    L]``, ``b`` and ``c`` ``[rows, N, L]`` (B and C broadcast over the
    lanes)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    g_all = s_ref.shape[0]
    per = max(k for k in range(1, TILES + 1) if g_all % k == 0)
    for g0 in range(0, g_all, per):
        tiles = range(g0, g0 + per)
        a = [a_ref[g] for g in tiles]  # [N, L] each

        def step(t, hs, tiles=tiles, a=a):
            b, c = b_ref[t], c_ref[t]
            out = []
            for g, a_g, h in zip(tiles, a, hs):
                dt = dt_ref[t, pl.ds(g, 1), :]  # [1, L]
                dx = dt * x_ref[t, pl.ds(g, 1), :]
                h = jnp.exp(dt * a_g) * h + dx * b
                y_ref[t, pl.ds(g, 1), :] = jnp.sum(
                    h * c, axis=0, keepdims=True
                )
                out.append(h)
            return tuple(out)

        hs = jax.lax.fori_loop(
            0, rows, step, tuple(s_out_ref[g] for g in tiles)
        )
        for g, h in zip(tiles, hs):
            s_out_ref[g] = h


def _scan_call(dt, x, b, c, a, stack, *, layer: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    s, t, g, lanes = dt.shape
    n = a.shape[1]
    rows = min(t, ROWS)
    pad = -t % rows
    if pad:  # rows of dt = 0 change nothing
        dt, x, b, c = (
            jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
            for v in (dt, x, b, c)
        )
    per_rows = lambda w: pl.BlockSpec(  # noqa: E731
        (None, rows, w, lanes), lambda i, j: (i, j, 0, 0)
    )
    state_spec = pl.BlockSpec(
        (None, None, g, n, lanes), lambda i, j: (layer, i, 0, 0, 0)
    )
    y, stack = pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows),
        grid=(s, (t + pad) // rows),
        in_specs=[
            per_rows(g), per_rows(g), per_rows(n), per_rows(n),
            pl.BlockSpec((g, n, lanes), lambda i, j: (0, 0, 0)),
            state_spec,
        ],
        out_specs=[per_rows(g), state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((s, t + pad, g, lanes), f32),
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(dt, x, b, c, a, stack)
    return y[:, :t], stack


# jitted under the names a device trace calls the kernel's events by (the
# innermost scope round the call names them: ops.paged_attn._jitted)
def ssm_step(*args, **kwargs):
    return _scan_call(*args, **kwargs)


def ssm_scan(*args, **kwargs):
    return _scan_call(*args, **kwargs)


_CALLS = {
    name: jax.jit(fn, static_argnames=("layer", "interpret"))
    for name, fn in (("ssm_step", ssm_step), ("ssm_scan", ssm_scan))
}


def scan(
    dt: Array,  # [S, T, E] float32, 0 where a row is padding or idle
    x: Array,  # [S, T, E] float32
    b: Array,  # [S, T, N] float32
    c: Array,  # [S, T, N] float32
    a: Array,  # [G, N, L] float32: A in the state's layout (to_layout)
    stack: Array,  # [Ll, S, G, N, L] float32: every layer's states
    layer: int,  # STATIC: which of them this call reads and writes
    name: str,  # "ssm_step" (decode) | "ssm_scan" (prefill)
    interpret: bool = False,  # a test's choice, never the program's
) -> tp.Tuple[Array, Array]:
    """S slots' T rows each: ``(y [S, T, E] float32, stack)``."""
    s, t, e = dt.shape
    _, _, g, n, lanes = stack.shape
    dt4, x4 = (v.astype(jnp.float32).reshape(s, t, g, lanes) for v in (dt, x))
    if interpret or (kernel_shapes_ok(g, n, lanes) and is_tpu_backend()):
        bl, cl = (
            jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                             (s, t, n, lanes))
            for v in (b, c)
        )
        y, stack = _CALLS[name](
            dt4, x4, bl, cl, a, stack, layer=layer, interpret=interpret
        )
        return y.reshape(s, t, e), stack
    y, new = scan_reference(dt4, x4, b.astype(jnp.float32),
                            c.astype(jnp.float32), a, stack[layer])
    return y.reshape(s, t, e), stack.at[layer].set(new)

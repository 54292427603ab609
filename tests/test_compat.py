"""The installed JAX's surface this package calls directly (there is no
shim layer: ``requirements.txt`` names the one installation). A JAX
upgrade that moves one of these fails here with one pointed test
instead of a wall of unrelated failures: the keyword surface of
``jax.shard_map`` (``axis_names`` = the MANUAL axes, ``check_vma``),
``pltpu.CompilerParams`` and its fields, ``pl.ANY``, the varying-axes
promotion in parallel.pipeline, and the jaxpr call-primitive names the
analysis provers recurse through."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _mesh1d():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("x",))


def test_shard_map_basic_map_and_collective():
    """The plain surface (mesh/in_specs/out_specs keywords) maps
    per-shard and runs collectives; a psum'd output passes the
    replication check (check_vma=True is the default)."""
    mesh = _mesh1d()
    double = shard_map(
        lambda a: a * 2, mesh=mesh, in_specs=(P("x"),), out_specs=P("x")
    )
    np.testing.assert_array_equal(
        np.asarray(double(jnp.arange(8))), 2 * np.arange(8)
    )
    total = shard_map(
        lambda a: jax.lax.psum(a, "x"),
        mesh=mesh,
        in_specs=(P("x"),),
        out_specs=P(),
        check_vma=True,
    )
    np.testing.assert_allclose(np.asarray(total(jnp.arange(8.0))), [28.0])


def test_shard_map_axis_names_with_axis_index():
    """``axis_names`` (the partial-manual surface) with a body that
    takes ``jax.lax.axis_index`` — the PP stage id and the
    sharded-dropout offsets are exactly this combination."""
    mesh = _mesh1d()
    f = shard_map(
        lambda a: a + jax.lax.axis_index("x").astype(a.dtype),
        mesh=mesh,
        in_specs=(P("x"),),
        out_specs=P("x"),
        axis_names={"x"},
    )
    np.testing.assert_array_equal(
        np.asarray(f(jnp.zeros((8,), jnp.int32))), np.arange(8)
    )


def test_shard_map_partial_manual_leaves_other_axes_to_gspmd():
    """Manual over 'p' only: the body sees the operand's FULL 'd' extent
    (GSPMD keeps sharding it underneath), which is what lets the PP
    region leave fsdp/tensor sharding to the partitioner."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("p", "d"))
    seen = {}

    def body(a):
        seen["shape"] = a.shape
        return a + jax.lax.axis_index("p").astype(a.dtype)

    x = jax.device_put(
        jnp.zeros((2, 8), jnp.int32),
        jax.sharding.NamedSharding(mesh, P("p", "d")),
    )
    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("p"),), out_specs=P("p"),
        axis_names={"p"},
    ))(x)
    assert seen["shape"] == (1, 8)
    np.testing.assert_array_equal(
        np.asarray(out), np.repeat(np.arange(2)[:, None], 8, axis=1)
    )


def test_pallas_tpu_names_the_kernels_use():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=1 << 20
    )
    assert p.dimension_semantics == ("parallel",)
    assert p.vmem_limit_bytes == 1 << 20
    assert pl.ANY is not None
    assert callable(pltpu.PrefetchScalarGridSpec)


def test_call_primitive_names_the_provers_align():
    """analysis.choreo recurses ALIGNED (operand origins carried into
    the body) only through the call primitives it lists by name; a
    rename degrades the provers to "no attention region found" — the
    jax 0.9 ``pjit`` -> ``jit`` rename did exactly that."""
    from midgpt_tpu.analysis.choreo import _ALIGNED_CALLS

    def prog(x):
        y = jax.jit(lambda a: a + 1)(x)
        y, _ = jax.lax.scan(lambda c, _: (c * 2, None), y, None, length=2)
        return jax.lax.while_loop(
            lambda c: c.sum() < 0, lambda c: c + 1, y
        )

    names = [
        e.primitive.name for e in jax.make_jaxpr(prog)(jnp.ones(3)).eqns
    ]
    assert names == ["jit", "scan", "while"]
    assert set(names) <= _ALIGNED_CALLS


def test_to_varying_is_value_identity():
    from midgpt_tpu.parallel.pipeline import _to_varying

    mesh = _mesh1d()
    x = jnp.arange(8.0)
    y = shard_map(
        lambda a: _to_varying(a, "x"), mesh=mesh, in_specs=(P(),),
        out_specs=P("x"),
    )(x)
    np.testing.assert_array_equal(np.asarray(y), np.tile(np.asarray(x), 8))


def test_to_varying_inside_manual_region():
    """_to_varying composes inside a manual shard_map region (where the
    pipeline uses it): the promoted value feeds a collective without
    changing its contents."""
    mesh = _mesh1d()
    from midgpt_tpu.parallel.pipeline import _to_varying

    def body(a):
        return jax.lax.psum(_to_varying(a, "x"), "x")

    f = shard_map(
        body, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(np.asarray(f(jnp.arange(8.0))), [28.0])

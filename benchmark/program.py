"""The one place that turns the benchmark's files into the program's own
objects: a configuration file into ``ModelConfig`` / ``ExperimentConfig``,
and the benchmark's weights into the program's ``GPT`` pytree. The kinds
import the system under test through here."""

from __future__ import annotations

import dataclasses
import typing as tp

import jax

from benchmark import weights


def model_config(sizes, knobs: tp.Optional[tp.Mapping[str, tp.Any]] = None):
    from midgpt_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in sizes.items() if k in fields}
    kw.update(knobs or {})
    return ModelConfig(**kw)


def experiment_config(sizes, knobs, **run):
    """``sizes``: the configuration file (model sizes and the published
    optimizer settings at its top level); ``knobs``: the cell's ``program``
    group (``model.*`` keys go to the model)."""
    from midgpt_tpu.config import ExperimentConfig, MeshConfig

    knobs = dict(knobs or {})
    model = model_config(sizes, knobs.pop("model", None))
    mesh = MeshConfig(**knobs.pop("mesh", {}))
    fields = {f.name for f in dataclasses.fields(ExperimentConfig)}
    kw = {k: v for k, v in sizes.items()
          if k in fields and k not in ("model", "mesh")}
    kw.update(knobs)
    kw.update(run)
    return ExperimentConfig(model=model, mesh=mesh, **kw)


def _leaf_name(path: str) -> str:
    # "blocks/attn/wqkv/weight" -> "wqkv"; "wte/weight" -> "wte"
    return path.split("/")[-2]


def model_leaves(model) -> tp.Dict[str, jax.Array]:
    """A ``GPT``-shaped pytree (parameters, or Adam's moments) as the
    benchmark's flat dict of leaves."""
    from midgpt_tpu.pytree import tree_paths

    return {_leaf_name(p): v for p, v in tree_paths(model)}


def fill_model(w: tp.Mapping[str, jax.Array], mcfg):
    """The program's ``GPT`` holding the benchmark's arrays ``w``."""
    from midgpt_tpu.models import GPT
    from midgpt_tpu.pytree import tree_paths

    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), mcfg))
    names = [_leaf_name(p) for p, _ in tree_paths(shape)]
    want = jax.tree.leaves(shape)
    assert sorted(names) == sorted(weights.LEAVES), names
    for n, s in zip(names, want):
        assert w[n].shape == s.shape, (n, w[n].shape, s.shape)
    return jax.tree.unflatten(jax.tree.structure(shape), [w[n] for n in names])

"""Unit tests for the AST TPU-footgun lint (analysis.pylint_pass), plus
the enforcement test that keeps the shipped tree lint-clean — the
"zero unwaived findings on midgpt_tpu/" acceptance bar, made permanent.
"""

import pathlib
import textwrap

import midgpt_tpu
from midgpt_tpu.analysis.pylint_pass import lint_paths, lint_source, unwaived


def _lint(src: str):
    return lint_source(textwrap.dedent(src), path="probe.py")


def _rules(findings):
    return [(f.rule, f.lineno) for f in findings if not f.waived]


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------


def test_item_in_jitted_function_flagged():
    fs = _lint(
        """
        import jax

        def step(state, x):
            return state, x.item()

        train = jax.jit(step, donate_argnums=(0,))
        """
    )
    assert _rules(fs) == [("host-sync-in-jit", 5)]


def test_host_sync_in_scan_body_flagged():
    fs = _lint(
        """
        import jax

        def body(carry, xs):
            v = jax.device_get(xs)
            return carry, v

        out = jax.lax.scan(body, 0, None)
        """
    )
    assert ("host-sync-in-jit", 5) in _rules(fs)


def test_np_asarray_in_traced_code_flagged():
    fs = _lint(
        """
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            return np.asarray(x) + n
        """
    )
    assert ("host-sync-in-jit", 8) in _rules(fs)


def test_transitive_reference_into_jit_is_traced():
    """jax.jit(wrapped) -> wrapped references step_fn -> step_fn's body
    is traced too (the make_train_step shape)."""
    fs = _lint(
        """
        import jax

        def step_fn(state, x):
            return state, x.item()

        def wrapped(state, x):
            return step_fn(state, x)

        train = jax.jit(wrapped, donate_argnums=(0,))
        """
    )
    assert ("host-sync-in-jit", 5) in _rules(fs)


def test_host_code_not_flagged():
    fs = _lint(
        """
        import numpy as np

        def load(path):
            x = np.asarray(open(path).read())
            return x.item()
        """
    )
    assert _rules(fs) == []


def test_jnp_asarray_not_flagged():
    fs = _lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.asarray(x)
        """
    )
    assert _rules(fs) == []


# ---------------------------------------------------------------------------
# unknown-mesh-axis
# ---------------------------------------------------------------------------


def test_unknown_axis_literal_flagged():
    fs = _lint(
        """
        from jax.sharding import PartitionSpec as P

        spec = P("fsdp", "tenzor")
        """
    )
    assert _rules(fs) == [("unknown-mesh-axis", 4)]


def test_known_axes_and_tuples_pass():
    fs = _lint(
        """
        from jax.sharding import PartitionSpec as P

        a = P(None, ("replica", "fsdp"), "sequence")
        b = P("pipeline", "fsdp", "tensor")
        """
    )
    assert _rules(fs) == []


def test_non_spec_strings_not_checked():
    fs = _lint(
        """
        def shard_act(x, *names):
            return x

        y = shard_act(None, "batch", "seq", "embed")  # logical, not mesh
        """
    )
    assert _rules(fs) == []


# ---------------------------------------------------------------------------
# missing-donate
# ---------------------------------------------------------------------------


def test_jit_on_state_fn_without_donation_flagged():
    fs = _lint(
        """
        import jax

        def step(state, x):
            return state

        train = jax.jit(step)
        """
    )
    assert _rules(fs) == [("missing-donate", 7)]


def test_jit_with_donation_passes():
    fs = _lint(
        """
        import jax

        def step(state, x):
            return state

        train = jax.jit(step, donate_argnums=(0,))
        """
    )
    assert _rules(fs) == []


def test_non_state_jit_not_flagged():
    fs = _lint(
        """
        import jax

        def eval_fn(params, xs):
            return xs

        ev = jax.jit(eval_fn)
        """
    )
    assert _rules(fs) == []


def test_decorated_state_fn_flagged():
    fs = _lint(
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def step(state, n):
            return state
        """
    )
    assert _rules(fs) == [("missing-donate", 5)]


# ---------------------------------------------------------------------------
# no-model-closure-jit (serving modules only)
# ---------------------------------------------------------------------------


def _lint_serving(src: str):
    return lint_source(
        textwrap.dedent(src), path="midgpt_tpu/serving/probe.py"
    )


_CLOSURE_SRC = """
    import jax

    def build(model):
        def window_fn(pool, logits):
            return model(pool), logits

        return jax.jit(window_fn, donate_argnums=(0,))
    """


def test_model_closure_in_serving_flagged():
    fs = _lint_serving(_CLOSURE_SRC)
    assert ("no-model-closure-jit", 8) in _rules(fs)


def test_model_closure_outside_scoped_files_not_flagged():
    """The rule covers midgpt_tpu/serving/ plus the train-side jit
    sites (train.py / bench.py) — other modules may close over
    config-derived structures."""
    fs = lint_source(
        textwrap.dedent(_CLOSURE_SRC), path="midgpt_tpu/train_probe.py"
    )
    assert [(r, n) for r, n in _rules(fs) if r == "no-model-closure-jit"] == []


def test_model_closure_in_train_py_flagged():
    """train.py's jit sites are in scope: a train program closing over
    the model would constant-fold the params into the executable and
    break donation (the PR 6 serving bug class, train-side)."""
    fs = lint_source(textwrap.dedent(_CLOSURE_SRC), path="midgpt_tpu/train.py")
    assert ("no-model-closure-jit", 8) in _rules(fs)


def test_model_closure_in_bench_py_flagged():
    fs = lint_source(textwrap.dedent(_CLOSURE_SRC), path="bench.py")
    assert ("no-model-closure-jit", 8) in _rules(fs)


def test_unrolled_layer_loop_rule_stays_serving_scoped():
    """Extending the closure rule to train.py must NOT drag the
    layer-loop rule along — train.py's loop structure is gated by the
    train dispatch budget, not the AST lint."""
    src = """
        import jax

        def loss(layers, x):
            for layer in layers:
                x = attention(layer, x)
            return x
        """
    fs = lint_source(textwrap.dedent(src), path="midgpt_tpu/train.py")
    assert [(r, n) for r, n in _rules(fs) if r == "no-unrolled-layer-loop"] == []


def test_model_as_parameter_passes():
    fs = _lint_serving(
        """
        import jax

        def build():
            def window_fn(model, pool, logits):
                return model(pool), logits

            return jax.jit(window_fn, donate_argnums=(1,))
        """
    )
    assert _rules(fs) == []


def test_model_closure_lambda_flagged():
    fs = _lint_serving(
        """
        import jax

        def build(model, window_fn):
            return jax.jit(lambda pool: window_fn(model, pool))
        """
    )
    assert ("no-model-closure-jit", 5) in _rules(fs)


def test_model_closure_decorator_flagged():
    fs = _lint_serving(
        """
        import functools
        import jax

        def build(model):
            @functools.partial(jax.jit, donate_argnums=(0,))
            def window_fn(pool):
                return model(pool)

            return window_fn
        """
    )
    assert ("no-model-closure-jit", 6) in _rules(fs)


def test_model_closure_not_hidden_by_nested_local_binding():
    """A nested helper that binds its OWN local `model` must not mask a
    genuine capture by the jitted function (scope-aware free-variable
    analysis — a flat bound set would swallow the real finding)."""
    fs = _lint_serving(
        """
        import jax

        def build(model):
            def window_fn(pool):
                def helper(x):
                    model = x * 2
                    return model

                return helper(pool) + model.wte

            return jax.jit(window_fn)
        """
    )
    assert any(r == "no-model-closure-jit" for r, _ in _rules(fs))


def test_nested_def_model_parameter_not_flagged():
    """A nested def whose PARAMETER is named model binds it in its own
    scope — the jitted function captures nothing."""
    fs = _lint_serving(
        """
        import jax

        def build():
            def window_fn(pool):
                def helper(model):
                    return model + 1

                return helper(pool)

            return jax.jit(window_fn)
        """
    )
    assert _rules(fs) == []


def test_model_closure_waivable():
    fs = _lint_serving(
        """
        import jax

        def build(model):
            def warm_fn(pool):
                return model(pool)

            return jax.jit(warm_fn)  # shardlint: disable=no-model-closure-jit
        """
    )
    assert _rules(fs) == []
    assert any(
        f.rule == "no-model-closure-jit" and f.waived for f in fs
    )


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------


def test_pragma_waives_named_rule():
    fs = _lint(
        """
        import jax

        def step(state, x):
            return state

        train = jax.jit(step)  # shardlint: disable=missing-donate
        """
    )
    assert _rules(fs) == []
    assert [(f.rule, f.waived) for f in fs] == [("missing-donate", True)]


def test_bare_pragma_waives_all():
    fs = _lint(
        """
        from jax.sharding import PartitionSpec as P

        spec = P("tenzor")  # shardlint: disable
        """
    )
    assert _rules(fs) == []


def test_pragma_on_other_line_does_not_waive():
    fs = _lint(
        """
        import jax
        # shardlint: disable=missing-donate

        def step(state, x):
            return state

        train = jax.jit(step)
        """
    )
    assert _rules(fs) == [("missing-donate", 8)]


# ---------------------------------------------------------------------------
# no-unrolled-layer-loop (serving modules only)
# ---------------------------------------------------------------------------


_LAYER_LOOP_SRC = """
    import jax

    def build(cfg):
        def window_fn(model, pool):
            h = pool
            for i in range(cfg.n_layer):
                h = model.block(h, i)
            return h

        return jax.jit(window_fn, donate_argnums=(1,))
    """


def test_unrolled_layer_loop_in_serving_flagged():
    fs = _lint_serving(_LAYER_LOOP_SRC)
    assert ("no-unrolled-layer-loop", 7) in _rules(fs)


def test_unrolled_layer_loop_outside_serving_not_flagged():
    """Scoped to midgpt_tpu/serving/: the layer loop of each serving
    program lives in its models/ forward on purpose."""
    fs = lint_source(
        textwrap.dedent(_LAYER_LOOP_SRC), path="midgpt_tpu/models/probe.py"
    )
    assert [
        (r, n) for r, n in _rules(fs) if r == "no-unrolled-layer-loop"
    ] == []


def test_unrolled_layer_loop_untraced_not_flagged():
    """A host-side loop over layers (checkpoint surgery, stats) is not
    a jitted program body — only traced roots are in scope."""
    fs = _lint_serving(
        """
        def describe(cfg, params):
            out = []
            for i in range(cfg.n_layer):
                out.append(params[i].shape)
            return out
        """
    )
    assert _rules(fs) == []


def test_unrolled_layer_loop_waivable():
    fs = _lint_serving(
        """
        import jax

        def build(cfg):
            def window_fn(model, pool):
                h = pool
                for i in range(cfg.n_layer):  # shardlint: disable=no-unrolled-layer-loop
                    h = model.block(h, i)
                return h

            return jax.jit(window_fn, donate_argnums=(1,))
        """
    )
    assert _rules(fs) == []  # _rules filters to unwaived findings


def test_non_layer_loop_in_serving_not_flagged():
    fs = _lint_serving(
        """
        import jax

        def build(cfg):
            def window_fn(model, pool):
                h = pool
                for i in range(4):
                    h = h + model.step(h)
                return h

            return jax.jit(window_fn, donate_argnums=(1,))
        """
    )
    assert _rules(fs) == []


# ---------------------------------------------------------------------------
# the shipped tree stays clean
# ---------------------------------------------------------------------------


def test_midgpt_tpu_tree_is_lint_clean():
    """The acceptance bar of the analysis PR, kept as an invariant:
    zero unwaived findings over the whole package. New waivers must be
    explicit inline pragmas, which show up in diffs."""
    pkg = pathlib.Path(midgpt_tpu.__file__).parent
    findings = unwaived(lint_paths([pkg]))
    assert findings == [], "\n".join(str(f) for f in findings)

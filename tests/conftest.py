"""Test environment: force an 8-device CPU platform BEFORE jax import so
multi-device sharding (DP/FSDP/SP/TP) is exercised without TPU hardware
(SURVEY.md 4: the reference's mesh code silently assumes >= 8 devices)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)  # (train.py:16)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.parallel.mesh import create_mesh

    return create_mesh(MeshConfig(replica=1, fsdp=2, sequence=2, tensor=2))


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Run Pallas kernels through the CPU interpreter (the tests' only way
    to execute TPU kernels without hardware)."""
    import functools

    from jax.experimental import pallas as pl

    from midgpt_tpu.ops import paged_attn

    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True)
    )
    # the paged kernels' calls are jitted: a trace made with one
    # ``pl.pallas_call`` would serve the next test's other one
    paged_attn.clear_traces()
    yield
    paged_attn.clear_traces()


@pytest.fixture
def paged_hook(monkeypatch):
    """``paged_hook(name, value)`` sets one of ops.paged_attn's test
    hooks (``_FORCE_BAND_PAGES``, ``_BAND_FOLD_ORDER``,
    ``SCORE_ACC_DTYPE``) for this test. The kernels' calls are jitted and
    keep a trace per shape, so the traces go when a hook is set and again
    before it is restored."""
    from midgpt_tpu.ops import paged_attn

    def set_hook(name, value):
        monkeypatch.setattr(paged_attn, name, value)
        paged_attn.clear_traces()

    yield set_hook
    paged_attn.clear_traces()


@pytest.fixture(scope="session")
def family_config():
    """``family_config(family)``: the configuration of one model family's
    own small test model (its test file's) — "dense", "block",
    "gated_delta", "latent" or "mamba"."""
    def config(family):
        if family == "dense":
            import test_serving as t

            return t.CFG
        if family == "block":
            import test_block_diffusion as t

            return t.CFG
        if family == "latent":
            import test_latent_serving as t

            return t.CFG
        import test_hybrid_serving as t

        if family == "gated_delta":
            return t.CFG
        assert family == "mamba", family
        from benchmark import program

        return program.model_config(t.SSM_SIZES)

    return config


# ---------------------------------------------------------------------------
# Smoke tier (r5, VERDICT r4 Weak #9): `pytest -m smoke` runs the
# oracle-parity + contract core in ~2 min so the build loop doesn't pay the
# full suite's ~25 min per iteration. The full suite stays the round gate.
# ---------------------------------------------------------------------------

_SMOKE_ALL = {
    "test_bench_contract",
    "test_layers",
    "test_sharding",
    "test_metrics",
    "test_gcs_paths",
    "test_data",
    "test_auto_knobs",
}
_SMOKE_TESTS = {
    "test_loss": {"test_chunked_xent_matches_dense_value_and_grads"},
    "test_flash": {
        "test_flash_forward_matches_naive",
        "test_flash_grad_matches_naive",
        "test_flash_dropout_matches_hash_oracle",
    },
    "test_ring": {
        "test_ring_matches_full_attention",
        "test_ring_dropout_matches_single_device_mask",
    },
    "test_model": {
        "test_batched_forward_matches_reference_math",
        "test_causality",
    },
    "test_pipeline": {"test_pipeline_forward_matches_sequential"},
    "test_sampling": {"test_decode_matches_full_forward"},
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        name = item.name.split("[", 1)[0]
        if mod in _SMOKE_ALL or name in _SMOKE_TESTS.get(mod, ()):
            item.add_marker(pytest.mark.smoke)
    # test_chip_compile.py loads the TPU's compiler into the process that
    # runs it. CPU programs compiled in that process AFTERWARDS were seen
    # to differ from their twins in the last float bit: the spill
    # bit-identity tests of test_serving_longctx.py failed in 5 of 39 runs
    # behind it and in 0 of 34 without it (PR 21). So it goes last — the
    # last file a worker is handed, and the last of a single process —
    # and nothing shares a process with the compiler after it has loaded.
    items.sort(key=lambda it: it.module.__name__.endswith("test_chip_compile"))


# ---------------------------------------------------------------------------
# Wall-clock accounting: tier-1 runs under a hard timeout (ROADMAP.md's
# 870 s verify line), and the budget has been breached by slow boxes
# before (PR 7's CHANGES entry). Print the top-10 slowest CALL phases at
# the end of every session so a test drifting toward the ~20 s
# move-to-slow-tier threshold is visible in every run's output instead
# of discovered by a timeout. (pytest's own --durations is opt-in;
# this makes the accounting permanent.)
# ---------------------------------------------------------------------------

_CALL_DURATIONS: list = []
_DESELECTED_SLOW: dict = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _CALL_DURATIONS.append((report.duration, report.nodeid))


def pytest_deselected(items):
    # tally slow-tier tests that were collected but deselected (the
    # `-m 'not slow'` tier-1 runs), per file — so the tier split of a
    # new test family is visible in every CI log instead of only in an
    # explicit `-m slow` collection
    for item in items:
        if item.get_closest_marker("slow") is not None:
            key = item.nodeid.split("::", 1)[0]
            _DESELECTED_SLOW[key] = _DESELECTED_SLOW.get(key, 0) + 1


def pytest_terminal_summary(terminalreporter):
    # SUITE_TIMING_OUT=path: also write the accounting as a JSON
    # artifact (CI uploads it; analysis/ledger.py ingests it via
    # --suite-timing, so tier-1 wall-time drift is tracked in the
    # perf trajectory like any other metric)
    out = os.environ.get("SUITE_TIMING_OUT")
    if out:
        import json

        top = sorted(_CALL_DURATIONS, reverse=True)[:10]
        payload = {
            "kind": "suite",
            "suite_total_call_s": round(
                sum(d for d, _ in _CALL_DURATIONS), 2
            ),
            "suite_n_calls": len(_CALL_DURATIONS),
            "slowest": [
                {"nodeid": nodeid, "s": round(dur, 2)}
                for dur, nodeid in top
            ],
            "deselected_slow": dict(sorted(_DESELECTED_SLOW.items())),
        }
        os.makedirs(
            os.path.dirname(os.path.abspath(out)), exist_ok=True
        )
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
    if _DESELECTED_SLOW:
        total_slow = sum(_DESELECTED_SLOW.values())
        terminalreporter.write_sep(
            "-",
            f"slow tier: {total_slow} collected-but-skipped test(s) "
            "this session (run with -m slow / in their CI jobs)",
        )
        for path in sorted(_DESELECTED_SLOW):
            terminalreporter.write_line(
                f"{_DESELECTED_SLOW[path]:4d}  {path}"
            )
    if not _CALL_DURATIONS:
        return
    top = sorted(_CALL_DURATIONS, reverse=True)[:10]
    total = sum(d for d, _ in _CALL_DURATIONS)
    terminalreporter.write_sep(
        "-",
        f"slowest 10 of {len(_CALL_DURATIONS)} test calls "
        f"(sum {total:.0f}s; non-slow tests >20s belong on the slow tier)",
    )
    for dur, nodeid in top:
        terminalreporter.write_line(f"{dur:8.2f}s  {nodeid}")

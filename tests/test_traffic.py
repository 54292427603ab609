"""HBM traffic auditor tests (analysis/traffic.py + analysis/budgets.py).

Fast tier: the analytic floor decomposition reproduces PERF.md's
hand-computed 124M B=8 numbers, classification/budget logic against
canned inputs.

Slow tier: compile the real decode window at audit size, gate it
against its checked-in budget, and re-introduce the PR 6
closed-over-model bug — the budget gate (not just the dequant rule)
must trip on it, from both directions: the weight stream vanishing
from the entry interface AND the executable bloating with baked-in
constants.
"""

import dataclasses

import pytest

from midgpt_tpu.analysis.budgets import (
    AUDIT_GEOMETRY,
    BUDGETS,
    budget_for,
    check_budget,
    geometry_key,
)
from midgpt_tpu.analysis.traffic import (
    TrafficReport,
    floor_decomposition,
    floor_table_markdown,
    parse_large_constants,
    traffic_report,
    weight_stream_bytes,
)
from midgpt_tpu.config import get_config


# ---------------------------------------------------------------------------
# analytic floor: reproduce PERF.md's decomposition
# ---------------------------------------------------------------------------


def test_floor_reproduces_perf_124m_decomposition():
    """PERF.md r5: 124M B=8, mean 640 live tokens, ~800 GB/s ->
    ~0.31 ms weights. The auditor must land within 5%."""
    cfg = get_config("openwebtext").model
    d = floor_decomposition(cfg, slots=8, live_tokens=640)
    assert abs(d["weights_floor_ms"] - 0.31) / 0.31 < 0.05
    # KV: the floor streams K AND V (both are read every step:
    # L * S * Hkv * live * C * 2 bytes * 2 planes); PERF's r5 prose "~0.12 ms" counted
    # the pair as one plane. Both conventions must be reproduced: the
    # honest stream within 5% of 2x the prose figure, and the prose
    # figure as exactly half the reported stream.
    assert abs(d["kv_floor_ms"] - 2 * 0.12) / (2 * 0.12) < 0.05
    assert abs(d["kv_floor_ms"] / 2 - 0.12) / 0.12 < 0.05
    # that formula, verbatim
    expect_kv = cfg.n_layer * 8 * cfg.kv_heads * 640 * cfg.head_dim * 2 * 2
    assert d["kv_bytes_per_step"] == expect_kv


def test_floor_reproduces_perf_quant_weights():
    """PERF.md PR 6: int8 moves the 124M weight stream 0.31 -> ~0.155."""
    cfg = get_config("openwebtext").model
    d = floor_decomposition(cfg, slots=8, live_tokens=640, quant=True)
    assert abs(d["weights_floor_ms"] - 0.155) / 0.155 < 0.05


def test_weight_stream_matches_count_params():
    """The analytic weight stream is count_params(model) * 2 at bf16,
    bit-exactly at audit size."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT, count_params
    from midgpt_tpu.pytree import cast_floating

    cfg = dataclasses.replace(
        get_config("openwebtext").model,
        n_layer=2, block_size=256, vocab_size=1024,
    )
    model = cast_floating(GPT.init(jax.random.PRNGKey(0), cfg), jnp.bfloat16)
    assert weight_stream_bytes(cfg) == count_params(model) * 2


def test_tp_divides_per_chip_streams():
    cfg = get_config("openwebtext").model
    d1 = floor_decomposition(cfg, slots=8, live_tokens=640)
    d2 = floor_decomposition(cfg, slots=8, live_tokens=640, tp_degree=2)
    assert d2["weights_bytes_per_step"] == d1["weights_bytes_per_step"] // 2
    assert d2["kv_bytes_per_step"] == d1["kv_bytes_per_step"] // 2


def test_floor_table_renders():
    cfg = get_config("openwebtext").model
    rows = [
        floor_decomposition(cfg, slots=8, live_tokens=640),
        floor_decomposition(cfg, slots=8, live_tokens=640, quant=True),
    ]
    md = floor_table_markdown(rows)
    assert "| B=8 live=640 bf16 |" in md
    assert "0.309" in md and "0.155" in md


# ---------------------------------------------------------------------------
# classification + budget logic (canned inputs, jax-free)
# ---------------------------------------------------------------------------

_CANNED_HLO = """\
HloModule probe, input_output_alias={ {0}: (1, {}, may-alias) }, \
entry_computation_layout={(bf16[2,768,2304]{2,1,0}, s8[2,3072,768]{2,1,0}, \
bf16[2,8,12,64,16]{4,3,2,1,0}, f32[4,1024]{1,0}, s32[4,16]{1,0}, \
f32[99,99]{1,0})->f32[4,1024]{1,0}}

ENTRY main {
  c0 = bf16[1024,768]{1,0} constant({...})
  c1 = f32[16]{0} constant({...})
  ROOT t = f32[4,1024]{1,0} parameter(3)
}
"""


def _canned_report(**overrides):
    keys = {
        "weights": {
            ("bf16", (2, 768, 2304)), ("s8", (2, 3072, 768)),
        },
        "kv": {("bf16", (2, 8, 12, 64, 16))},
        "logits": {("f32", (4, 1024))},
    }
    kw = dict(
        program="decode_window", stream_keys=keys, window_steps=4,
        comms_bytes=0,
    )
    kw.update(overrides)
    return traffic_report(_CANNED_HLO, **kw)


def test_classification_bins_by_dtype_and_shape():
    rep = _canned_report()
    assert rep.streams["weights"] == (
        2 * 768 * 2304 * 2 + 2 * 3072 * 768 * 1
    )
    assert rep.streams["kv"] == 2 * 8 * 12 * 64 * 16 * 2
    assert rep.streams["logits"] == 4 * 1024 * 4
    assert rep.streams["control"] == 4 * 16 * 4
    # the f32[99,99] matches nothing -> surfaced, not silently binned
    assert rep.unclassified == (("f32", (99, 99)),)
    # the big bf16 constant is counted; the 16-element one is noise
    assert rep.streams["constants"] == 1024 * 768 * 2
    assert rep.weights_bytes_per_dispatch == rep.streams["weights"] * 4


def test_parse_large_constants_threshold():
    consts = parse_large_constants(_CANNED_HLO, min_bytes=4096)
    assert consts == [("bf16", (1024, 768))]
    assert ("f32", (16,)) in parse_large_constants(
        _CANNED_HLO, min_bytes=1
    )


def _mk_report(weights, kv=1000, logits=100, constants=0, comms=0,
               unclassified=()):
    return TrafficReport(
        program="probe",
        streams={
            "weights": weights, "kv": kv, "logits": logits,
            "control": 0, "constants": constants,
        },
        window_steps=1,
        comms_bytes=comms,
        unclassified=tuple(unclassified),
    )


_BUDGET = {
    "weights": 10000, "kv": 1000, "logits": 100,
    "constants_max": 500, "comms_max": 50,
}


def test_budget_passes_in_band():
    assert check_budget(_mk_report(weights=10100), _BUDGET) == []


def test_budget_trips_on_missing_weight_stream():
    """The PR 6 signature: weights leave the entry interface."""
    bad = check_budget(_mk_report(weights=0), _BUDGET)
    assert any("weights stream" in v for v in bad)


def test_budget_trips_on_doubled_weight_stream():
    bad = check_budget(_mk_report(weights=20000), _BUDGET)
    assert any("weights stream" in v for v in bad)


def test_budget_trips_on_baked_constants():
    bad = check_budget(
        _mk_report(weights=10000, constants=100000), _BUDGET
    )
    assert any("constant" in v for v in bad)


def test_budget_trips_on_comms_blowup():
    bad = check_budget(_mk_report(weights=10000, comms=5000), _BUDGET)
    assert any("collective" in v for v in bad)


def test_budget_trips_on_unclassified_param():
    bad = check_budget(
        _mk_report(weights=10000, unclassified=[("f32", (99, 99))]),
        _BUDGET,
    )
    assert any("unclassified" in v for v in bad)


def test_geometry_keys():
    assert geometry_key(None) == "single"
    assert geometry_key({}) == "single"
    assert geometry_key({"tensor": 2, "replica": 2}) == "replica2,tensor2"
    assert geometry_key({"tensor": 2, "replica": 1}) == "tensor2"


def test_budget_table_covers_all_programs_and_precisions():
    programs = {"decode_window", "prefill_chunk", "verify_program"}
    # SP prefill only exists on sharded meshes (tensor > 1), so its cells
    # appear under the tp geometry only.
    sharded = programs | {"prefill_chunk_sp"}
    for geom in ("single", "replica2,tensor2"):
        want = sharded if geom == "replica2,tensor2" else programs
        for precision in ("bf16", "int8"):
            have = {
                p for (p, q, g) in BUDGETS
                if q == precision and g == geom
            }
            assert have == want, (precision, geom, have)
    assert AUDIT_GEOMETRY["config"] == "openwebtext"


# ---------------------------------------------------------------------------
# slow tier: real compiles — the gate passes on the tree, trips on the
# PR 6 closure
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_audit_traffic_within_checked_in_budget():
    from midgpt_tpu.analysis.harness import audit_decode_window

    _, report, traf = audit_decode_window(
        "openwebtext", slots=4, window=4, page_size=16, traffic=True
    )
    assert report.ok
    budget = budget_for("decode_window", "bf16", "single")
    assert check_budget(traf, budget) == [], check_budget(traf, budget)


@pytest.mark.slow
def test_budget_cells_invariant_to_banding(paged_hook):
    """Banding moves ZERO bytes (ISSUE 20): the banded PV fold slices
    the same streams the unbanded reduction read — each K/V byte still
    crosses HBM exactly once per pass — so every decode-window traffic
    cell must land in the SAME checked-in budget band with a genuinely
    multi-banded plan forced as with the auto plan (one band at this
    geometry), and the two audits' classified per-stream totals must be
    byte-identical."""
    from midgpt_tpu.analysis.harness import audit_decode_window

    _, report, traf = audit_decode_window(
        "openwebtext", slots=4, window=4, page_size=16, traffic=True
    )
    assert report.ok
    paged_hook("_FORCE_BAND_PAGES", 2)
    _, report_b, traf_b = audit_decode_window(
        "openwebtext", slots=4, window=4, page_size=16, traffic=True
    )
    assert report_b.ok
    budget = budget_for("decode_window", "bf16", "single")
    assert check_budget(traf_b, budget) == [], check_budget(traf_b, budget)
    assert dict(traf_b.streams) == dict(traf.streams), (
        traf_b.streams, traf.streams
    )


@pytest.mark.slow
def test_model_closure_trips_budget_gate():
    """Re-introduce the PR 6 bug: a decode window that CLOSES OVER the
    model instead of taking it as an entry parameter. The weights leave
    the program interface (below the weights band) and reappear as
    baked-in constants (above the constants cap) — the budget gate must
    trip on BOTH, independent of any HLO shape pattern."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.analysis.harness import (
        _serving_audit_setup, serving_stream_keys,
    )
    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.serving.engine import make_decode_window

    cfg = get_config("openwebtext")
    # extra-tiny geometry: the closure bakes every weight into the
    # compiled module's TEXT, so keep the model small
    tiny = dataclasses.replace(
        cfg,
        model=ModelConfig(
            block_size=64, vocab_size=128, n_layer=1, n_head=4,
            n_embd=64, dropout=0.0, remat="none", scan_unroll=1,
        ),
    )
    slots, window, page_size = 2, 2, 16
    model_cfg, mesh, model, pmax, pool, logits, _, _ = (
        _serving_audit_setup(
            tiny, slots=slots, page_size=page_size, shrink=False
        )
    )
    keys = serving_stream_keys(model, pool, logits)
    window_fn = make_decode_window(
        model, slots=slots, window=window, pmax=pmax,
        rope_len=model_cfg.block_size,
    )
    i32 = lambda *s: np.zeros(s, np.int32)  # noqa: E731
    args = (
        pool, logits, i32(slots, pmax), i32(slots),
        np.zeros((slots,), bool), i32(slots), i32(slots), i32(slots),
        i32(slots), jax.random.PRNGKey(1),
    )

    # healthy program: model as entry parameter -> measure its budget
    healthy_hlo = window_fn.lower(model, *args).compile().as_text()
    healthy = traffic_report(
        healthy_hlo, program="decode_window", stream_keys=keys,
        window_steps=window,
    )
    budget = {
        "weights": healthy.streams["weights"],
        "kv": healthy.streams["kv"],
        "logits": healthy.streams["logits"],
        "constants_max": max(4096, healthy.streams["constants"]),
    }
    assert healthy.streams["weights"] > 0
    assert check_budget(healthy, budget) == []

    # the PR 6 bug, verbatim: close over the model
    closed = jax.jit(lambda *a: window_fn(model, *a))
    bad_hlo = closed.lower(*args).compile().as_text()
    bad = traffic_report(
        bad_hlo, program="decode_window", stream_keys=keys,
        window_steps=window,
    )
    violations = check_budget(bad, budget)
    assert any("weights stream" in v for v in violations), violations
    assert any("constant" in v for v in violations), violations


# ---------------------------------------------------------------------------
# int8-quantized KV pool cells (PR 9)
# ---------------------------------------------------------------------------


def test_kv8_budget_cells_exist_for_every_program():
    from midgpt_tpu.analysis.budgets import precision_key

    for prog in ("decode_window", "prefill_chunk", "verify_program"):
        for prec in ("bf16", "int8"):
            for geom in ("single", "replica2,tensor2"):
                cell = budget_for(prog, precision_key(prec, True), geom)
                assert cell is not None, (prog, prec, geom)
                assert "kv" in cell and "constants_max" in cell


def test_kv8_cells_carry_half_the_bf16_kv_stream():
    """The point of the int8 pool, in budget arithmetic: every kv8 cell's
    KV stream is the bf16 cell's payload halved plus the f32
    per-(page, KV-head) scale planes — and the scale overhead is small
    (< 1% of the payload at the audit geometry). The bf16 cells are
    untouched."""
    from midgpt_tpu.analysis.budgets import precision_key

    for prog in ("decode_window", "prefill_chunk", "verify_program"):
        for geom in ("single", "replica2,tensor2"):
            for prec in ("bf16", "int8"):
                base = budget_for(prog, prec, geom)
                kv8 = budget_for(prog, precision_key(prec, True), geom)
                scales = kv8["kv"] - base["kv"] // 2
                assert 0 < scales < base["kv"] // 100, (
                    prog, prec, geom, kv8["kv"], base["kv"]
                )
                # weights are orthogonal: kv-quant must not move them
                assert kv8["weights"] == base["weights"]


def test_precision_key():
    from midgpt_tpu.analysis.budgets import precision_key

    assert precision_key("bf16") == "bf16"
    assert precision_key("int8", False) == "int8"
    assert precision_key("bf16", True) == "bf16-kv8"
    assert precision_key("int8", True) == "int8-kv8"


def test_floor_decomposition_kv_quant_halves_kv_stream():
    """The analytic roofline with the int8 pool: KV bytes drop to half
    plus the per-page scale term, moving the 124M B=8 int8-weights floor
    from ~0.39 (0.155 w + 0.236 kv) toward ~0.27 ms/step (0.155 +
    0.118) — the PR 9 target arithmetic (PERF.md)."""
    cfg = get_config("openwebtext").model
    base = floor_decomposition(cfg, slots=8, live_tokens=640, quant=True)
    kv8 = floor_decomposition(
        cfg, slots=8, live_tokens=640, quant=True, kv_quant=True
    )
    assert kv8["kv_quant"] is True
    payload_half = base["kv_bytes_per_step"] // 2
    scales = kv8["kv_bytes_per_step"] - payload_half
    assert 0 < scales < base["kv_bytes_per_step"] // 50
    assert kv8["weights_bytes_per_step"] == base["weights_bytes_per_step"]
    # the headline: int8 weights + int8 KV lands near the ~0.27 floor
    assert abs(kv8["floor_ms_per_step"] - 0.28) < 0.03
    assert abs(base["floor_ms_per_step"] - 0.39) < 0.03
    # the floor table renders the kv8 tag
    table = floor_table_markdown([kv8])
    assert "kv8" in table

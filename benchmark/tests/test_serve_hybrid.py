"""The rehearsal of kind ``serve_hybrid`` at a tiny size: the program comes out
correct against the plain reference of the model of full-attention and
gated-delta-rule layers, every stand-in comes out not correct, the counters the
cell's per-layer metrics read are there, and ``ops_hybrid`` gives by hand what
ISSUE 32 reckons for one layer of each kind."""

import json
import os

import pytest

from benchmark.tests import tiny
from benchmark.tests.test_controls import drive

KINDS = ["linear_attention"] * 3 + ["full_attention"]
SIZES = {
    "source": "test", "n_layer": 8, "layer_types": KINDS * 2, "n_head": 4,
    "n_kv_head": 4, "head_width": 16, "n_embd": 64, "block_size": 128,
    "vocab_size": 512, "dropout": 0.0, "mlp": "swiglu", "mlp_hidden": 128,
    "qk_norm": True, "qk_norm_kind": "rms_full", "rope_style": "none",
    "norm_scale": True, "norm_eps": 1e-6, "norm_order": "post",
    "tie_embeddings": False, "linear_key_heads": 4, "linear_value_heads": 4,
    "linear_key_dim": 8, "linear_value_dim": 16, "linear_conv": 4,
    "linear_neg_eigval": True, "reduced": [],
}

CELL = {
    "kind": "serve_hybrid", "config": "tiny-hybrid", "chips": 1,
    "why": "test",
    "traffic_params": {
        "loop": "closed", "clients": 3, "pool": 8, "sizes_seed": 0,
        "prompt_len": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                       "min": 8, "max": 64},
        "output_len": {"dist": "fixed", "value": 32},
        "distinct_first_token": True, "shared_prefix": 0, "ramp_steps": 4},
    "program": {"attn_impl": "naive"},
    "engine": {"slots": 3, "num_pages": 24, "window": 4, "prefill_chunk": 16,
               "prefill_budget": 32, "temperature": 0.0,
               "paged_kernel": "xla"},
    "check_requests": 8, "check_length": 96,
    "trace": {"start_share": 0.2, "seconds": 0.5},
    # CPU readings at this size over eight seeds (bf16 at a width of 64 and
    # keys of 8, some 250 tokens a run; my runs, PR 32): the program 0.51 ..
    # 1.36 / 0.028 .. 0.068 (widest, mean: the delta rule at keys of 8 feels
    # its input's bf16 rounding far more than at the published 96), the
    # int8-rounded reference at least 2.51 / 0.50, the int4 one 5.56 / 2.44,
    # one altered token 3.17 / 0.142, a state left from the slot's last
    # request 3.64 / 0.86
    "limits": {"served_logit_gap": 1.9, "served_gap_mean": 0.1},
}

NAME = "tiny-hybrid-serve"


@pytest.fixture(scope="module")
def hybrid_copy(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if not any(w["name"] == NAME for w in bench["workloads"]):
        tiny.add_cell(copy, bench, NAME, "tiny-hybrid", SIZES, CELL)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "serve-olmo-hybrid-decode" in m.get("workloads", []):
                m["workloads"].append(NAME)
        tiny.write_bench(copy, bench)
    return copy


def test_program_is_correct_and_counts(hybrid_copy):
    _, res, err = tiny.run(hybrid_copy, NAME, trace=1, seconds=2.0,
                           seed=2147480011)
    assert res["correct"] is True, (res["compared"], err[-2000:])
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    assert 0.0 < m["recurrent_bytes_share.serve"]["value"] < 100.0
    assert 0.0 < m["slot_occupancy.serve"]["value"] <= 100.0
    # a rehearsal reads no device: no roofline share of either kind
    assert "gdn_step_roofline.serve" not in m
    assert "decode_stream_roofline.serve" not in m
    stats = json.loads(err[err.index("{", err.index(
        "engine counters over the window")):].splitlines()[0].replace(
            "'", '"'))
    assert stats["state_resets"] >= res["attempted"] > 0
    assert stats["recurrent_slot_steps"] > 0
    assert stats["recurrent_state_bytes"] == 6 * 3 * (4 * 8 * 16 * 4
                                                      + 3 * 128 * 2)


def test_the_cells_before_it_read_none_of_its_metrics(hybrid_copy):
    """``tiny.make_copy`` hands every serving metric to ``tiny-serve`` too,
    as the driver hands this PR's files to the parent: the readers find
    nothing there and say nothing."""
    _, res, _ = tiny.run(hybrid_copy, "tiny-serve", trace=1, seconds=1.0)
    assert res["correct"] is True
    assert not [k for k in res["metrics"] if "recurrent" in k or "gdn" in k]


@pytest.mark.parametrize("stand_in", [
    "ref_int8",        # the control: below the stated bf16
    "altered_token",
    "stale_state",
])
def test_stand_in_is_not_correct(hybrid_copy, monkeypatch, stand_in):
    res = drive(hybrid_copy, monkeypatch, NAME, "--stand-in", stand_in)
    assert res["correct"] is False
    over = [n for n, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over, res["compared"]


def test_reference_rule_is_the_recurrence_by_hand():
    """Two tokens of one head through ``reference_hybrid.delta_rule``,
    against the equations worked in numpy."""
    import numpy as np

    from benchmark import reference_hybrid as ref

    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 2, 1, 3)).astype(np.float32)
    v = rng.normal(size=(2, 1, 2)).astype(np.float32)
    g = np.array([[-0.5], [-0.1]], np.float32)
    beta = np.array([[1.5], [0.3]], np.float32)
    s = np.zeros((3, 2), np.float32)
    want = []
    for t in range(2):
        s = np.exp(g[t, 0]) * s
        u = beta[t, 0] * (v[t, 0] - s.T @ k[t, 0])
        s = s + np.outer(k[t, 0], u)
        want.append(s.T @ q[t, 0])
    o, last = ref.delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o)[:, 0], want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(last)[0], s, rtol=1e-5)


def test_yardstick_arithmetic_by_hand():
    """``ops_hybrid`` at the benchmark configuration's sizes, against the
    arithmetic of ISSUE 32 worked by hand."""
    from benchmark import ops_hybrid

    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "configs", "olmo-hybrid-7b-l16.json")) as f:
        sizes = json.load(f)
    d, f_, v = 3840, 11008, 100352
    mlp = 3 * d * f_  # 126.8 M
    linear = (2 * d * 2880 + 2 * d * 5760 + 5760 * d + 2 * d * 30) + mlp
    full = 4 * d * d + mlp
    assert ops_hybrid.layers(sizes) == (12, 4)
    assert ops_hybrid.linear_layer_params(sizes) == linear
    assert ops_hybrid.full_layer_params(sizes) == full
    assert abs(linear / 1e6 - 215.5) < 0.1 and abs(full / 1e6 - 185.8) < 0.1
    params = 12 * linear + 4 * full + d * v
    assert ops_hybrid.matmul_params(sizes) == params
    assert abs((params + d * v) * 2 / 1e9 - 8.20) < 0.01  # with the embedding
    # a slot's state in one layer: 30 heads of 96 x 192 in float32, 2.21 MB;
    # the step kernel reads and writes 32 slots' worth a call
    state = 30 * 96 * 192
    assert ops_hybrid.state_bytes_per_call(sizes, 32) == 2 * 32 * state * 4
    # and with the tail (3 x 11520 in bf16), twelve layers, both ways
    rec = ops_hybrid.recurrent_stream_bytes(sizes, 32)
    assert rec == 2 * 12 * 32 * (state * 4 + 3 * 11520 * 2)
    assert abs(rec / 1e9 - 1.75) < 0.01
    # a decode step at 32 slots of 1,200 live positions: 11.5 GB
    step = ops_hybrid.decode_stream_bytes(sizes, 32, 32 * 1200.0)
    kv = 32 * 1200 * 2 * 4 * 30 * 128 * 2
    assert step == 2 * params + kv + rec
    assert abs(step / 1e9 - 11.54) < 0.01
    # a row's forward: 2 a matrix element, 4 dk dv a head a linear layer,
    # attention over the context in the four full layers only
    row = ops_hybrid.row_forward_flops(sizes, 1000.0)
    assert row == 2.0 * params + 4.0 * 12 * state + 4.0 * 4 * 30 * 128 * 1000
    assert ops_hybrid.prompt_flops(sizes, 9) == 9 * (
        ops_hybrid.row_forward_flops(sizes, 5.0))

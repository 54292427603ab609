"""The rehearsal of kind ``serve_latent`` at a tiny size (documents of 64
tokens): the program comes out correct against the plain reference of the
latent-attention expert model, every stand-in comes out not correct, the
counters the cell's per-layer metrics read are there and are what a known
schedule gives by hand, and ``ops_latent`` gives by hand what ISSUE 34 reckons
for one layer of each kind."""

import json
import os

import numpy as np
import pytest

from benchmark.tests import tiny
from benchmark.tests.test_controls import drive

SIZES = {
    "source": "test", "n_layer": 3, "n_head": 4, "n_embd": 64,
    "block_size": 192, "vocab_size": 512, "dropout": 0.0,
    "attention": "latent", "latent_q": 24, "latent_kv": 16, "latent_nope": 8,
    "latent_rope": 4, "latent_v": 8, "mlp": "experts", "dense_layers": 1,
    "mlp_hidden": 96, "experts": 8, "experts_per_token": 2,
    "expert_hidden": 16, "expert_renorm": True, "expert_scoring": "sigmoid",
    "expert_bias": True, "expert_scale": 2.5, "shared_experts": 1,
    "qk_norm": False, "rope_style": "interleaved", "rope_base": 32000000.0,
    "norm_scale": True, "norm_eps": 1e-6, "tie_embeddings": False,
    "reduced": [],
}

CELL = {
    "kind": "serve_latent", "config": "tiny-latent", "chips": 1,
    "why": "test",
    "traffic_params": {
        "loop": "closed", "clients": 4, "pool": 8, "sizes_seed": 0,
        "documents": 2, "document_len": 64,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 48},
        "output_len": {"dist": "fixed", "value": 32},
        "distinct_first_token": True, "shared_prefix": 0, "ramp_steps": 6},
    "program": {},
    # 2 documents of 16 pages, 4 slots of at most 21 of their own, and what
    # the index keeps of finished requests until pressure reclaims it
    "engine": {"slots": 4, "page_size": 4, "num_pages": 160, "window": 4,
               "prefill_chunk": 16, "prefill_budget": 64, "temperature": 0.0,
               "max_prefills_per_window": 2, "paged_kernel": "xla"},
    "check_requests": 2, "check_length": 192,
    "trace": {"start_share": 0.2, "seconds": 0.5},
    # CPU readings at this size (bf16 at a width of 64, a latent of 16, 64
    # served tokens a run; my runs, PR 34). The program over 10 seeds: at
    # most 1.07 / 0.0195 (widest, mean: top-2 of 8 experts at a width of 64
    # flips on a bf16 rounding). Over 4 seeds: the int4-rounded reference at
    # least 4.0 / 1.6 (the int8 one 0.29 .. 0.65 / 0.018 .. 0.052: this width
    # barely feels int8, as tiny-serve's), the row's width in the softmax
    # 0.94 / 0.145, a stale page in the middle of every document 1.29 /
    # 0.117, one altered token of 64 2.87 / 0.0975
    "limits": {"served_logit_gap": 1.8, "served_gap_mean": 0.05},
}

NAME = "tiny-latent-docs"
CELL_METRICS = (
    "mla_decode_roofline.serve", "latent_stream_roofline.serve",
    "latent_bytes_share.serve", "shared_reread_share.serve",
    "prefix_hit_share.serve")


@pytest.fixture(scope="module")
def latent_copy(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if not any(w["name"] == NAME for w in bench["workloads"]):
        tiny.add_cell(copy, bench, NAME, "tiny-latent", SIZES, CELL)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "serve-joyai-flash-docs" in m.get("workloads", []):
                m["workloads"].append(NAME)
        tiny.write_bench(copy, bench)
    return copy


def _stats(err):
    return json.loads(err[err.index("{", err.index(
        "engine counters over the window")):].splitlines()[0].replace(
            "'", '"'))


def test_program_is_correct_and_counts(latent_copy):
    _, res, err = tiny.run(latent_copy, NAME, trace=1, seconds=2.0,
                           seed=2147480011)
    assert res["correct"] is True, (res["compared"], err[-2000:])
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    # a prompt is a document of 64 and a question of 8 to 48: what is saved
    # is the document, 57 to 89 % of it
    assert 57.0 < m["prefix_hit_share.serve"]["value"] < 89.0
    assert 0.0 < m["latent_bytes_share.serve"]["value"] < 100.0
    # 4 slots over 2 documents: two walk each document's pages at a time
    assert 20.0 < m["shared_reread_share.serve"]["value"] < 50.0
    assert 0.0 < m["experts_touched_share.serve"]["value"] <= 100.0
    assert m["expert_load_max_over_mean.serve"]["value"] >= 1.0
    # a rehearsal reads no device: no roofline share of either kind
    assert "mla_decode_roofline.serve" not in m
    assert "latent_stream_roofline.serve" not in m
    stats = _stats(err)
    assert stats["expert_rows_dropped"] == 0 and stats["evictions"] == 0
    assert stats["latent_layers"] == 3
    assert stats["prefill_tokens_saved"] % 64 == 0
    assert stats["expert_layer_forwards"] == stats["windows"] * 4 * 2
    assert 0 < stats["kv_pages_distinct"] < stats["kv_pages_walked"]


def test_the_cells_before_it_read_none_of_its_metrics(latent_copy):
    """``tiny.make_copy`` hands every serving metric to ``tiny-serve`` too,
    as the driver hands this PR's files to the parent: the readers find
    nothing there and say nothing."""
    _, res, _ = tiny.run(latent_copy, "tiny-serve", trace=1, seconds=1.0)
    assert res["correct"] is True
    assert not [k for k in res["metrics"] if k in CELL_METRICS]


@pytest.mark.parametrize("stand_in", [
    "ref_int4",        # the control at this width (tiny-serve's too)
    "wrong_scale",
    "altered_token",
    "stale_page",
])
def test_stand_in_is_not_correct(latent_copy, monkeypatch, stand_in):
    res = drive(latent_copy, monkeypatch, NAME, "--stand-in", stand_in)
    assert res["correct"] is False
    over = [n for n, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over, res["compared"]


def test_a_program_without_latent_attention_refuses_the_cell_at_once(
        latent_copy, monkeypatch):
    """What the parent commit does under this PR's files: its ``ModelConfig``
    drops the keys it does not know, and the kind refuses by name before
    anything is built."""
    from benchmark import program

    real = program.model_config

    def parents(sizes, knobs=None):
        drop = ("attention", "dense_layers", "shared_experts")
        return real({k: v for k, v in sizes.items()
                     if not k.startswith(("latent_", "expert_s", "expert_b"))
                     and k not in drop}, knobs)

    monkeypatch.setattr(program, "model_config", parents)
    with pytest.raises(ValueError, match="knows latent attention"):
        drive(latent_copy, monkeypatch, NAME)


def test_shares_by_hand_for_a_known_schedule():
    """Two slots on one document of 16 pages, 3 and 5 pages of their own: a
    step walks 40 pages, 24 of them distinct, so 40 % of the walk reads a
    page another slot reads; and of prompts of 64 + 12 and 64 + 20, the
    second a hit, 64 of 160 tokens were saved."""
    import jax
    import jax.numpy as jnp

    from benchmark.kinds.serve_latent import fill_model
    from benchmark import program, weights_latent
    from midgpt_tpu.serving import ServingEngine

    mcfg = program.model_config(SIZES)
    model = fill_model(weights_latent.make(
        jax.random.PRNGKey(0), SIZES, jnp.float32), mcfg)
    eng = ServingEngine(model, slots=2, page_size=4, window=4,
                        prefill_chunk=16, prefill_budget=128,
                        paged_kernel="xla", cache_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 510, size=64)
    first = np.concatenate([doc, rng.integers(0, 510, size=12)])
    second = np.concatenate([doc, rng.integers(0, 510, size=20)])
    eng.submit(first.astype(np.int32), 8)
    eng.step()  # prefills 76 tokens, and one window: 76 + 4 resident
    eng.submit(second.astype(np.int32), 8)
    st0 = eng.stats()
    eng.step()  # the hit: 20 tokens prefilled; both decode one window
    st = eng.stats()
    assert st["prefill_tokens_saved"] == 64
    assert st["prompt_tokens_total"] == 76 + 84
    assert st["prefill_tokens_saved"] / st["prompt_tokens_total"] == 0.4
    # at the dispatch slot 0 held 80 tokens = 20 pages, slot 1 84 = 21
    walked = st["kv_pages_walked"] - st0["kv_pages_walked"]
    distinct = st["kv_pages_distinct"] - st0["kv_pages_distinct"]
    assert (walked, distinct) == (4 * 41, 4 * 25)
    assert 1 - distinct / walked == pytest.approx(16 / 41)


def test_yardstick_arithmetic_by_hand():
    """``ops_latent`` at the benchmark configuration's sizes, against the
    arithmetic of ISSUE 34 worked by hand."""
    from benchmark import ops_latent

    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "configs", "joyai-llm-flash-l5.json")) as f:
        sizes = json.load(f)
    attn = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
            + 4096 * 2048)
    assert ops_latent.attention_params(sizes) == attn
    assert abs(attn / 1e6 - 26.35) < 0.01
    expert = 3 * 2048 * 768  # 4.72 M
    assert ops_latent.expert_params(sizes) == expert
    dense = attn + 3 * 2048 * 7168  # 70.4 M
    assert ops_latent.dense_layer_params(sizes) == dense
    assert abs(dense / 1e6 - 70.4) < 0.05
    fixed = attn + 2048 * 256 + expert  # attention, router, shared expert
    assert ops_latent.expert_layer_fixed_params(sizes) == fixed
    assert ops_latent.layers(sizes) == (1, 4)
    # the whole model: one dense layer, four expert layers of 256 experts,
    # the embedding and the head: 5,558 M parameters, 11.12 GB in bf16
    head = 2048 * 129280
    whole = dense + 4 * (fixed + 256 * expert) + 2 * head
    assert abs(whole / 1e6 - 5558) < 1 and abs(whole * 2 / 1e9 - 11.12) < 0.01
    # a token's cache in a layer: 576 values, 1,152 bytes in bf16; a call
    # over 32 slots of 33.2 k tokens reads 1.22 GB
    assert ops_latent.latent_bytes_per_token(sizes) == 1152
    live = 32 * 33200.0
    assert ops_latent.latent_read_bytes_per_layer(sizes, live) == live * 1152
    assert abs(live * 1152 / 1e9 - 1.22) < 0.01
    # a decode step at 162 touched experts a layer (63 % of 256): the
    # latents 6.1 GB, the touched experts 6.1 GB, everything else 0.92 GB
    touched = 162.0
    weights = ops_latent.decode_weight_bytes(sizes, touched)
    assert weights == 2 * (dense + 4 * (fixed + touched * expert) + head)
    rest = 2 * (dense + 4 * fixed + head)
    assert abs(4 * touched * expert * 2 / 1e9 - 6.1) < 0.05
    assert abs(rest / 1e9 - 0.92) < 0.01
    step = ops_latent.decode_stream_bytes(sizes, touched, live)
    assert step == weights + 5 * live * 1152
    assert abs(step / 1e9 - 13.2) < 0.1
    assert ops_latent.latent_bytes_share(sizes, touched, live) == (
        5 * live * 1152 / step)
    # a row's forward in the published form: 2 a matrix element it
    # contracts against, 2 x 32 x (192 + 128) a key a layer
    active = dense + 4 * (fixed + 8 * expert) + head
    assert ops_latent.active_matmul_params(sizes) == active
    row = ops_latent.row_forward_flops(sizes, 1000.0)
    assert row == 2.0 * active + 2.0 * 5 * 32 * 320 * 1000
    # a hit's prompt: the 300 rows behind 32,768 cached positions
    assert ops_latent.prompt_flops(sizes, 33068, 32768) == 300 * (
        ops_latent.row_forward_flops(sizes, 32768 + 150.5))

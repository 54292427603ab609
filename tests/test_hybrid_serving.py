"""A model of full-attention and gated-delta-rule layers through
``ServingEngine`` — the same scheduler, allocator, decode window and
prefill-chunk programs as every other model, a fixed-size recurrent state a
slot beside the page pool — held to the plain reference of the architecture
(``benchmark/reference_hybrid.py``: float32, the rule a token at a time, no
cache) on seeded random weights at a small size: D 64, two periods of three
linear layers and a full one, 4 heads, keys of 8, values of 16, V 512. Logits
are compared, not sampled tokens; where tokens are compared the model is
float32 and the seeds leave no near-tie."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_hybrid as rh
from benchmark import weights_hybrid
from benchmark.kinds.serve_hybrid import fill_model
from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import ENGINE_STATS_KEYS, ServingEngine
from midgpt_tpu.serving.paged import PagedKVPool, RecurrentState

SIZES = dict(
    n_layer=8, layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    n_head=4, n_kv_head=4, head_width=16, n_embd=64, vocab_size=512,
    block_size=128, mlp_hidden=128, norm_eps=1e-6, linear_key_heads=4,
    linear_value_heads=4, linear_key_dim=8, linear_value_dim=16,
    linear_conv=4, linear_neg_eigval=True,
)
CFG = ModelConfig(
    mlp="swiglu", qk_norm=True, qk_norm_kind="rms_full", rope_style="none",
    norm_scale=True, norm_order="post", tie_embeddings=False,
    attn_impl="naive", remat="none", **SIZES,
)
NEW = 12
PROMPT_LENS = (37, 20, 55, 9, 70)  # one, two and five chunks of 16


@pytest.fixture(scope="module")
def weights():
    return weights_hybrid.make(jax.random.PRNGKey(3), SIZES, jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    return fill_model(weights, CFG)


# a model of the other recurrent mixer, Mamba layers beside attention
# (benchmark/weights_ssm.py, published names), for the refusals both share
SSM_SIZES = dict(
    hidden_size=32, intermediate_size=64, num_attention_heads=4,
    num_key_value_heads=1, vocab_size=512, num_hidden_layers=3,
    attn_layer_offset=1, attn_layer_period=3, mamba_d_state=16,
    mamba_expand=2, mamba_dt_rank=8, mamba_d_conv=4, rms_norm_eps=1e-6,
    n_layer=3, layer_types=("mamba", "full_attention", "mamba"), n_head=4,
    n_kv_head=1, head_width=8, n_embd=32, block_size=128, mlp="swiglu",
    mlp_hidden=64, qk_norm=False, rope_style="none", norm_scale=True,
    norm_eps=1e-6, tie_embeddings=True, attn_impl="naive", remat="none",
)


@pytest.fixture(scope="module")
def ssm_model():
    from benchmark import program, weights_ssm
    from benchmark.kinds import serve_ssm

    w = weights_ssm.make(jax.random.PRNGKey(5), SSM_SIZES, jnp.float32)
    return serve_ssm.fill_model(w, program.model_config(SSM_SIZES))


@pytest.fixture(scope="module", params=["gated_delta", "mamba"])
def recurrent(request, model, ssm_model):
    """Either recurrent mixer's model: the refusals are the engine's, for
    whatever holds a state no snapshot of exists."""
    return model if request.param == "gated_delta" else ssm_model


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 510, size=n).astype(np.int32) for n in PROMPT_LENS]


def engine(model, **kw):
    kw = {"slots": 2, "page_size": 16, "window": 4, "prefill_chunk": 16,
          "cache_dtype": jnp.float32, "paged_kernel": "xla", **kw}
    return ServingEngine(model, **kw)


def serve(model, prompts, new=NEW, **kw):
    eng = engine(model, **kw)
    rids = [eng.submit(p, new) for p in prompts]
    eng.run()
    return [list(eng.finished[r].tokens) for r in rids], eng


@pytest.fixture(scope="module")
def served(model, prompts):
    return serve(model, prompts)


def test_the_model_holds_one_stack_a_kind_and_a_plan():
    assert CFG.kv_layers == 2 and CFG.linear_layers == 6
    assert CFG.layer_plan[:5] == (
        ("linear", 0), ("linear", 1), ("linear", 2), ("full", 0),
        ("linear", 3))
    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), CFG))
    assert shape.blocks.ln1.weight.shape == (2, 64)
    assert shape.lin_blocks.attn.conv.shape == (6, 4, 2 * 32 + 64)
    # the pool's layer axis counts the full-attention layers only; the
    # recurrent state is a slot's, not a page's
    pool = jax.eval_shape(lambda: PagedKVPool.init(CFG, 8, 16))
    assert pool.k.shape == (2, 8, 16, 64)
    state = jax.eval_shape(lambda: RecurrentState.init(CFG, 3))
    assert state.s.shape == (6, 3, 4, 8, 16) and state.s.dtype == jnp.float32
    assert state.conv.shape == (6, 3, 3, 128)
    # a model whose layers are all full attention: the plan is the identity
    plain = dataclasses.replace(CFG, layer_types=None)
    assert plain.layer_plan == tuple(("full", i) for i in range(8))
    assert plain.kv_layers == 8 and plain.linear_layers == 0


def test_whole_sequence_forward_is_the_reference(weights, model, prompts):
    """``GPT.__call__`` (the chunked rule from an empty state) against the
    reference's token-by-token rule: float32 on both sides, so what is left
    is the order of sums — 64 tokens a chunk through a triangular solve
    against one at a time — under 1e-3 of logits of order 4."""
    seq = np.concatenate(prompts)[:128]
    want = rh.make_sequence_logits(SIZES)(weights, jnp.asarray(seq))
    got = jax.jit(lambda m, t: m(t))(model, jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=0)


def test_engine_logits_are_the_reference(weights, model, prompts):
    """Prefill in chunks of 16, then decode windows of one step: after every
    engine step the slot's logits row is the reference's full forward at that
    position. Tolerance 2e-3 on logits of order 4: float32 throughout, the
    sum of (a) the chunked rule against the token-by-token one, 1e-4 (the
    test above), (b) the state carried across five chunks and then a token
    at a time, another order of the same sums, 1e-4, (c) attention over
    pages plus recent rows in one softmax against the reference's, 1e-4, and
    (d) eight layers of it. The reference with int8-rounded operands, a
    precision below the stated one, misses it by three orders."""
    p = prompts[4]
    eng = engine(model, slots=1, window=1)
    # the plain order: a window dispatched ahead would move the row before
    # the step that reads it returns
    eng._dispatch_ahead = False
    rid = eng.submit(p, NEW)
    rows = []
    while eng.has_work:
        eng.step()
        if eng.decode_dispatches > len(rows):  # a window of one step ran
            rows.append(np.asarray(eng.logits[0]))
    toks = eng.finished[rid].tokens
    seq = np.concatenate([p, np.asarray(toks, np.int32)])
    want = np.asarray(rh.make_sequence_logits(SIZES)(weights, jnp.asarray(seq)))
    # the last chunk's row (behind position len(p) - 1) is sampled from in
    # the step that wrote it; rows[i] is the row behind position len(p) + i
    # (the row behind the LAST token is nobody's: the request is done, its
    # state no longer moves, and nothing is sampled from it)
    assert len(rows) == NEW
    got = np.stack(rows[:-1])
    np.testing.assert_allclose(got, want[len(p):-1], atol=2e-3, rtol=0)
    low = np.asarray(rh.make_sequence_logits(SIZES, quant="int8")(
        weights, jnp.asarray(seq)))
    assert np.abs(low[len(p):-1] - got).max() > 0.1
    # greedy: each served token is the reference's first choice
    assert toks == list(want[len(p) - 1:len(seq) - 1].argmax(-1))


@pytest.mark.parametrize("kw", [
    {"prefill_chunk": None}, {"prefill_chunk": 32, "prefill_budget": 64},
    {"window": 1}, {"window": 7}, {"slots": 1}, {"slots": 5},
], ids=lambda kw: "-".join(f"{k}{v}" for k, v in kw.items()))
def test_tokens_do_not_depend_on_the_schedule(model, prompts, served, kw):
    """Chunking, the window and how many slots there are move nothing: with
    one slot every request starts where another finished, from zeros all the
    same (the reset), and with five none does."""
    got, eng = serve(model, prompts, **kw)
    assert got == served[0]
    assert eng.stats()["state_resets"] == len(prompts)


def test_a_slot_starts_from_zeros_whoever_was_there(model, prompts, served):
    """Without the reset a request inherits its slot's last state: the
    planted fault the benchmark's ``stale_state`` stand-in runs, and the
    tokens show it."""
    eng = engine(model, slots=1)
    eng._admit_state = lambda s, req: None
    rids = [eng.submit(p, NEW) for p in prompts]
    eng.run()
    got = [list(eng.finished[r].tokens) for r in rids]
    assert got[0] == served[0][0]  # the first finds zeros anyway
    assert got[1:] != served[0][1:]


def test_a_merged_first_program_starts_from_zeros(model, prompts):
    """One slot, two requests: each request's first granules run as ONE
    program (70 tokens: 4 granules of 16 in the step that admits it, then
    6; 55: 4 granules), the state's ``fresh`` flag riding that merged
    program from position 0 — so the second request starts from zeros
    although the first left its state in the slot. The tokens are those of
    a granule a program."""
    pair = [prompts[4], prompts[2]]
    got, eng = serve(model, pair, slots=1)
    st = eng.stats()
    assert (st["prefill_chunks"], st["prefill_dispatches"]) == (9, 3)
    assert st["state_resets"] == 2
    want, one = serve(model, pair, slots=1, prefill_budget=16)
    assert one.stats()["prefill_dispatches"] == 9
    assert got == want


def test_evicted_request_prefills_again_and_continues(model, prompts, served):
    """Six pages for two slots: growth evicts, the evicted request comes
    back with prompt + generated tokens, prefilled from position 0 (no
    snapshot of the state exists), and continues to the same tokens."""
    got, eng = serve(model, prompts[:3], new=24, num_pages=6)
    want, _ = serve(model, prompts[:3], new=24)
    st = eng.stats()
    assert st["evictions"] > 0 and st["state_reprefill_tokens"] > 0
    assert st["state_resets"] == 3 + st["evictions"]
    assert got == want


def test_prefix_cache_is_accepted_and_does_nothing(recurrent, prompts):
    eng = engine(recurrent, prefix_cache=True)
    assert eng.index is None
    again = [prompts[0], prompts[0], prompts[2]]
    rids = [eng.submit(p, NEW) for p in again]
    eng.run()
    st = eng.stats()
    assert st["prefix_hits_refused"] == 1 and st["prefill_tokens_saved"] == 0
    assert st["prefill_tokens_computed"] == sum(len(p) for p in again)
    want, _ = serve(recurrent, [prompts[0], prompts[2]])
    assert [list(eng.finished[r].tokens) for r in rids] == [
        want[0], want[0], want[1]]


def test_counters_of_the_recurrent_state(served):
    _, eng = served
    st = eng.stats()
    assert set(st) == set(ENGINE_STATS_KEYS)
    assert st["state_resets"] == len(PROMPT_LENS)
    assert st["state_reprefill_tokens"] == 0
    # slot x linear layer x decode step, counted a window at a time
    assert st["recurrent_slot_steps"] % (6 * 4) == 0
    assert st["recurrent_slot_steps"] >= 6 * NEW * len(PROMPT_LENS)
    assert st["recurrent_state_bytes"] == 6 * 2 * (4 * 8 * 16 * 4 + 3 * 128 * 4)
    assert st["kv_bytes_live"] == 0  # nothing is resident once all finished


@pytest.mark.parametrize("name,kw", [
    ("speculate", {"speculate": 2}),
    ("quant", {"quant": "int8"}),
    ("kv_quant", {"kv_quant": "int8"}),
    ("role != 'both'", {"role": "prefill"}),
    ("spill", {"spill": "on"}),
    ("prefill_sp", {"prefill_sp": "on"}),
    ("layer_scan='on'", {"layer_scan": "on"}),
    ("temperature > 0", {"temperature": 0.7}),
])
def test_what_needs_a_snapshot_is_refused_by_name(recurrent, name, kw):
    with pytest.raises(ValueError, match="recurrent layers does not "
                       "support .*" + name.replace("(", r"\(")):
        engine(recurrent, **kw)


def test_a_mesh_and_the_handoff_are_refused_by_name(recurrent, prompts):
    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(MeshConfig(replica=1, fsdp=4, sequence=1, tensor=2))
    with pytest.raises(ValueError, match="does not support a mesh"):
        engine(recurrent, mesh=mesh)
    eng = engine(recurrent)
    eng.submit(prompts[1], 4)
    eng.step()
    with pytest.raises(ValueError, match="export_request"):
        eng.export_request(0)
    with pytest.raises(ValueError, match="import_request"):
        eng.import_request(None)

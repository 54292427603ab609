"""A latent-attention expert model through ``ServingEngine`` — the same
scheduler, allocator, prefix index, decode window and prefill-chunk programs
as every other model, a latent page pool of one payload a page — held to the
plain reference of the architecture (``benchmark/reference_latent.py``:
float32, the published form, no cache) on seeded random weights at a small
size: D 64, one dense and two expert layers, 4 heads, q rank 24, latent 16,
8 + 4 lanes a head, values of 8, 8 experts of 16 top-2 and one shared, pages
of 4, V 512. Logits are compared, not sampled tokens; where tokens are
compared the model is float32 and the seeds leave no near-tie."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_latent as rl
from benchmark import weights_latent
from benchmark.kinds.serve_latent import fill_model
from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import ENGINE_STATS_KEYS, ServingEngine
from midgpt_tpu.serving.paged import PagedKVPool

SIZES = dict(
    n_layer=3, n_head=4, n_embd=64, vocab_size=512, block_size=192,
    latent_q=24, latent_kv=16, latent_nope=8, latent_rope=4, latent_v=8,
    dense_layers=1, mlp_hidden=96, experts=8, experts_per_token=2,
    expert_hidden=16, expert_scale=2.5, shared_experts=1, norm_eps=1e-6,
    rope_base=32e6,
)
CFG = ModelConfig(
    attention="latent", mlp="experts", expert_scoring="sigmoid",
    expert_bias=True, qk_norm=False, norm_scale=True, tie_embeddings=False,
    remat="none", **SIZES,
)
NEW = 12
DOC = 64  # a shared document: 16 whole pages of 4
PROMPT_LENS = (37, 20, 55, 9, 70)  # one, two and five chunks of 16


@pytest.fixture(scope="module")
def weights():
    return weights_latent.make(jax.random.PRNGKey(3), SIZES, jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    return fill_model(weights, CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 510, size=n).astype(np.int32) for n in PROMPT_LENS]


def engine(model, **kw):
    kw = {"slots": 2, "page_size": 4, "window": 4, "prefill_chunk": 16,
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
    assert CFG.layer_plan == (("latent", 0), ("latent", 1), ("latent", 2))
    assert CFG.stack_plan == (
        ("dense_blocks", 0), ("blocks", 0), ("blocks", 1))
    assert CFG.kv_layers == 3 and CFG.expert_layers == 2
    assert CFG.latent_row == 128 and CFG.pool_heads == 1
    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), CFG))
    assert shape.dense_blocks.mlp.w_up.weight.shape == (1, 64, 96)
    assert shape.blocks.mlp.w_in.shape == (2, 8, 64, 32)
    assert shape.blocks.mlp.shared.w_up.weight.shape == (2, 64, 16)
    assert shape.blocks.attn.wkv_a.weight.shape == (2, 64, 20)
    # one payload a page, no KV-head axis, no V array; the layer axis counts
    # every latent layer
    pool = jax.eval_shape(lambda: PagedKVPool.init(CFG, 8, 4))
    assert pool.k.shape == (3, 8, 4, 128) and pool.v is None
    # the models that were there: the plan and the stacks they had
    plain = ModelConfig(block_size=64, vocab_size=64, n_layer=2, n_head=2,
                        n_embd=32)
    assert plain.layer_plan == (("full", 0), ("full", 1))
    assert plain.stack_plan == (("blocks", 0), ("blocks", 1))
    assert plain.pool_heads == 2 and plain.pool_width == plain.rope_dim == 16


def test_whole_sequence_forward_is_the_reference(weights, model, prompts):
    """``GPT.__call__`` (the published form, whole sequence) against the
    reference's: float32 on both sides, the same sums in XLA's order and in
    the reference's blocks — 1e-4 on logits of order 3."""
    seq = np.concatenate(prompts)[:128]
    want = rl.make_sequence_logits(SIZES)(
        weights, jnp.asarray(seq), jnp.arange(128))
    got = jax.jit(lambda m, t: m(t))(model, jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_engine_logits_are_the_reference(weights, model, prompts):
    """Prefill in chunks of 16 (absorbed, against pooled rows), then decode
    windows of one step (absorbed, pages + the window's rows in one softmax):
    after every engine step the slot's logits row is the reference's full
    forward — published form, nothing cached — at that position. Tolerance
    5e-4 on logits of order 3: float32 throughout, what is left is the order
    of the sums (q^ = q_nope Wuk^T first, then over the latent's 16 lanes,
    against up-projecting every key first) through three layers. The
    reference with int8-rounded operands, a precision below the stated one,
    and the reference that divides by the row's width (sqrt 20, not sqrt 12)
    both miss it by two orders."""
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
    seq = jnp.asarray(np.concatenate([p, np.asarray(toks, np.int32)]))
    at = jnp.arange(len(p), len(seq) - 1)
    want = np.asarray(rl.make_sequence_logits(SIZES)(weights, seq, at))
    assert len(rows) == NEW
    got = np.stack(rows[:-1])
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=0)
    for wrong in ({"quant": "int8"}, {"wrong_scale": True}):
        low = np.asarray(rl.make_sequence_logits(SIZES, **wrong)(
            weights, seq, at))
        assert np.abs(low - got).max() > 0.05, wrong
    # greedy: each served token is the reference's first choice
    first = np.asarray(rl.make_sequence_logits(SIZES)(
        weights, seq, jnp.arange(len(p) - 1, len(seq) - 1)))
    assert toks == list(first.argmax(-1))


@pytest.mark.parametrize("kw", [
    {"prefill_chunk": None}, {"prefill_chunk": 32, "prefill_budget": 64},
    {"window": 1}, {"window": 7}, {"slots": 1}, {"slots": 5},
    {"page_size": 8}, {"page_size": 16}, {"prefix_cache": False},
], ids=lambda kw: "-".join(f"{k}{v}" for k, v in kw.items()))
def test_tokens_do_not_depend_on_the_schedule(model, prompts, served, kw):
    """Chunking, the window, the page size, the slot and the prefix cache
    move nothing."""
    got, _ = serve(model, prompts, **kw)
    assert got == served[0]


def test_kernel_mode_serves_the_gather_paths_tokens(
        model, prompts, served, pallas_interpret):
    """The decode window through the paged kernel's latent mode (interpreted):
    the contract's clause 3, token for token."""
    got, eng = serve(model, prompts[:2], paged_kernel="pallas")
    assert eng.paged_kernel == "pallas"
    assert got == served[0][:2]


def _asks(prompts):
    rng = np.random.default_rng(5)
    doc = rng.integers(0, 510, size=DOC).astype(np.int32)
    return doc, [np.concatenate([doc, q]) for q in prompts[:3]]


def test_a_hit_on_a_shared_document_serves_the_same_tokens(model, prompts):
    """Three questions over one document, one after the other through one
    slot: the second and third find the document's 16 pages in the index,
    prefill their question only, and serve what they serve with the prefix
    cache off."""
    doc, asks = _asks(prompts)
    want, cold = serve(model, asks, slots=1, prefix_cache=False)
    got, eng = serve(model, asks, slots=1)
    assert got == want
    st = eng.stats()
    assert st["prefill_tokens_saved"] == 2 * DOC
    assert st["prompt_tokens_total"] == sum(len(a) for a in asks)
    assert cold.stats()["prefill_tokens_saved"] == 0
    assert st["prefill_tokens_computed"] == st["prompt_tokens_total"] - 2 * DOC


def test_a_merged_program_behind_a_hit_serves_the_same_tokens(
        model, prompts):
    """Behind a hit on the document (64 positions), a question's granules
    of one step run as ONE program from position 64 against the pooled
    document rows (55 tokens: four granules of 16 in one program), and
    serve the tokens of a granule a program."""
    doc, asks = _asks(prompts)
    asks = [asks[0], asks[2]]
    got, eng = serve(model, asks, slots=1)
    st = eng.stats()
    assert st["prefill_tokens_saved"] == DOC
    # the first ask: 64 + 37 tokens, 7 granules in two programs; the
    # second: 55 behind the hit, 4 granules in one
    assert (st["prefill_chunks"], st["prefill_dispatches"]) == (11, 3)
    want, one = serve(model, asks, slots=1, prefill_budget=16)
    assert one.stats()["prefill_dispatches"] == 11
    assert got == want


def test_two_requests_on_one_document_share_its_pages(model, prompts):
    """Two slots on one document hold its pages at refcount 2 and walk them
    twice; when they finish the pages go to the index's cold list, not to the
    free list; and the resident latent bytes count a shared page once."""
    doc, asks = _asks(prompts)
    eng = engine(model, slots=2)
    eng.submit(asks[0], NEW)
    eng.run()  # the document is the index's
    pages = eng.index.match(doc)[0]
    assert len(pages) == DOC // 4
    assert all(eng.alloc.refcount(p) == 0 for p in pages)
    assert eng.alloc.cached_pages >= len(pages)
    st0 = eng.stats()
    for a in asks[1:]:
        eng.submit(a, NEW)
    eng.step()
    assert all(eng.alloc.refcount(p) == 2 for p in pages)
    assert list(eng.bt[0, :16]) == list(eng.bt[1, :16]) == pages
    st = eng.stats()
    # every token of both tables, the document's once: 576 / 640 of a row
    # is the latent and the key, in float32 here
    live = int(eng.pooled_len.sum()) - DOC
    assert st["latent_bytes_live"] == live * 3 * (16 + 4) * 4
    assert st["latent_layers"] == 3
    eng.run()
    st = eng.stats()
    walked = st["kv_pages_walked"] - st0["kv_pages_walked"]
    distinct = st["kv_pages_distinct"] - st0["kv_pages_distinct"]
    assert 0 < distinct < walked
    # while both decode, a step walks the document's pages twice
    assert walked - distinct >= 16 * (NEW - 4)
    assert all(eng.alloc.refcount(p) == 0 for p in pages)
    assert eng.index.match(doc)[0] == pages  # still whole, still matchable
    assert st["cold_reclaims"] == 0 and st["latent_bytes_live"] == 0


def test_counters_of_the_expert_layers_and_the_latent_cache(served):
    _, eng = served
    st = eng.stats()
    assert tuple(st) == ENGINE_STATS_KEYS
    # the two expert layers and not the dense one: a live row claims two
    # experts in each, a window counts its steps' (layer, step)s
    assert st["expert_layer_forwards"] == st["windows"] * 4 * 2
    assert st["expert_rows_dropped"] == 0
    assert st["expert_rows_routed"] % 4 == 0
    # a slot decodes NEW - 1 tokens behind the one its prefill yields
    assert st["expert_rows_routed"] == len(PROMPT_LENS) * (NEW - 1) * 2 * 2
    assert 0 < st["experts_touched"] <= st["expert_layer_forwards"] * 8
    assert st["expert_rows_max"] >= st["expert_layer_forwards"]
    assert st["kv_bytes_live"] == 0 and st["latent_layers"] == 3


def test_a_mesh_and_the_other_kinds_of_layer_are_refused_by_name(model):
    import dataclasses

    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(MeshConfig(replica=1, fsdp=4, sequence=1, tensor=2))
    with pytest.raises(ValueError, match="does not support a mesh"):
        engine(model, mesh=mesh)
    blocky = GPT.init(jax.random.PRNGKey(0), dataclasses.replace(
        CFG, block_len=4, block_steps=4, mask_token=511))
    with pytest.raises(ValueError, match="does not support block_len"):
        engine(blocky)
    with pytest.raises(AssertionError, match="linear-attention"):
        dataclasses.replace(
            CFG, layer_types=("linear_attention",) * 2 + ("full_attention",))

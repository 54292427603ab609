"""A model of Mamba-1 and full-attention layers through ``ServingEngine`` —
the same scheduler, allocator, decode window and prefill-chunk programs as
every other model, the recurrent state a slot beside the page pool — held to
the plain reference of the architecture (``benchmark/reference_ssm.py``:
float32, the scan a token at a time, no cache) on seeded random weights at a
small size: D 32, E 64, N 16, R 8, two Mamba layers and one attention layer
(from the published offset / period: 1 of 3), 4 query heads over one KV
head, no positions, the head tied to the embedding, V 512. Logits are
compared, not sampled tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program, reference_ssm, weights_ssm
from benchmark.kinds.serve_ssm import fill_model
from midgpt_tpu.serving import ServingEngine

SIZES = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "vocab_size": 512, "num_hidden_layers": 3,
    "attn_layer_offset": 1, "attn_layer_period": 3, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "rms_norm_eps": 1e-6,
    "n_layer": 3, "n_head": 4, "n_kv_head": 1, "head_width": 8,
    "n_embd": 32, "block_size": 128, "mlp": "swiglu", "mlp_hidden": 64,
    "qk_norm": False, "rope_style": "none", "norm_scale": True,
    "norm_eps": 1e-6, "tie_embeddings": True, "attn_impl": "naive",
    "remat": "none",
}
SIZES["layer_types"] = [
    "full_attention" if k == "attention" else k
    for k in weights_ssm.layer_types(SIZES)
]
NEW = 10
PROMPT_LENS = (37, 20, 55)  # three, two and four chunks of 16


@pytest.fixture(scope="module")
def weights():
    return weights_ssm.make(jax.random.PRNGKey(4), SIZES, jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    return fill_model(weights, program.model_config(SIZES))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, 510, size=n).astype(np.int32) for n in PROMPT_LENS]


def engine(model, **kw):
    kw = {"slots": 2, "page_size": 16, "window": 4, "prefill_chunk": 16,
          "cache_dtype": jnp.float32, "paged_kernel": "xla", **kw}
    return ServingEngine(model, **kw)


def test_the_layer_order_is_the_published_reading():
    assert SIZES["layer_types"] == ["mamba", "full_attention", "mamba"]
    cfg = program.model_config(SIZES)
    assert cfg.recurrent_mixer == "mamba" and cfg.kv_layers == 1


def test_two_requests_side_by_side_are_the_reference(weights, model,
                                                     prompts):
    """Two slots, prefill in chunks of 16, then windows of one step: after
    every engine step each decoding slot's logits row is the reference's
    full forward at that position. Tolerance 1e-3 on logits of order 2:
    float32 throughout, what is left is the order of sums (the scan's
    carried state across chunks and steps, attention over pages and recent
    rows in one softmax). The reference without its inner norms misses by
    far more."""
    eng = engine(model, window=1)
    # the plain order: a window dispatched ahead would move the rows before
    # the step that reads them returns
    eng._dispatch_ahead = False
    rids = [eng.submit(p, NEW) for p in prompts[:2]]
    rows = {r: {} for r in rids}
    while eng.has_work:
        eng.step()
        lg = np.asarray(eng.logits)
        for s, req in enumerate(eng.slot_req):
            if req is not None and not eng.prefilling[s]:
                # the row behind the request's newest token
                rows[req.rid][len(req.prompt) + len(req.tokens) - 1] = lg[s]
    full = reference_ssm.make_sequence_logits(SIZES)
    plain = reference_ssm.make_sequence_logits(SIZES, inner_norms=False)
    for rid, p in zip(rids, prompts):
        toks = eng.finished[rid].tokens
        assert len(toks) == NEW
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        want = np.asarray(full(weights, jnp.asarray(seq)))
        # greedy: each served token is the reference's first choice
        assert toks == list(want[len(p) - 1:len(seq) - 1].argmax(-1))
        at = sorted(rows[rid])
        # a step that ends a prompt runs its first window too: the row
        # behind the prompt is sampled before it can be read
        assert at == list(range(len(p), len(seq) - 1))
        got = np.stack([rows[rid][i] for i in at])
        np.testing.assert_allclose(got, want[at], atol=1e-3, rtol=0)
        off = np.asarray(plain(weights, jnp.asarray(seq)))
        assert np.abs(off[at] - got).max() > 0.05


def test_a_slot_starts_from_zeros_whoever_was_there(model, prompts):
    """Without admission's reset a request inherits its slot's last state
    and tail: the planted fault the benchmark's ``stale_state`` stand-in
    runs, and the tokens show it."""
    want = []
    for p in prompts:
        eng = engine(model, slots=1)
        rid = eng.submit(p, NEW)
        eng.run()
        want.append(list(eng.finished[rid].tokens))
    eng = engine(model, slots=1)
    eng._admit_state = lambda s, req: None
    rids = [eng.submit(p, NEW) for p in prompts]
    eng.run()
    got = [list(eng.finished[r].tokens) for r in rids]
    assert got[0] == want[0]  # the first finds zeros anyway
    assert got[1:] != want[1:]
    st = eng.stats()
    assert st["state_resets"] == 0
    # the prefill chunks' rows: the prompts', against the buckets' (whole
    # pages of 16, a power of two of them)
    assert st["ssm_rows_scanned"] == sum(PROMPT_LENS)
    assert st["ssm_rows_scanned"] < st["ssm_rows_run"]
    assert st["ssm_rows_run"] % 16 == 0

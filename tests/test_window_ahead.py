"""A decode window enqueued before the host reads the one in front of it
(``ServingEngine._ahead``): wherever no slot can end in the window in
flight, every slot decodes, nobody waits for a slot and the pages are
there, the next window takes that window's lengths, ``done`` and ``emitted``
on the device. Held here to the plain order (``_dispatch_ahead = False``,
every window read before the next goes in): the same tokens per request and
the same counters, for a dense model greedy and sampled, and for a model of
each recurrent mixer (state carried on the device); no window goes ahead
where the rule forbids it; and what changes a slot between steps (cancel,
drain, a scripted fault) finds the window in flight read first. On a mesh
the window dispatched ahead runs the program compiled for the plain one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program, weights_hybrid, weights_latent, weights_ssm
from benchmark.kinds import serve_hybrid, serve_latent, serve_ssm
from midgpt_tpu.config import MeshConfig, ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.parallel.mesh import create_mesh
from midgpt_tpu.serving import ServingEngine
from midgpt_tpu.serving.faults import FaultPlan, ReplicaCrash

W = 4  # the decode window
# the counters a window ahead must count as the plain order does: a latent
# model's expert counters are device outputs of the window, the rest the host's
SAME = ("tokens_generated", "kv_pages_walked", "kv_pages_distinct",
        "occupancy_sum", "decode_dispatches", "device_reads",
        "expert_rows_routed", "expert_rows_max", "experts_touched")

DENSE = ModelConfig(
    block_size=96, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)
GDN_SIZES = dict(
    n_layer=2, layer_types=["linear_attention", "full_attention"],
    n_head=4, n_kv_head=4, head_width=16, n_embd=64, vocab_size=256,
    block_size=128, mlp_hidden=128, norm_eps=1e-6, linear_key_heads=4,
    linear_value_heads=4, linear_key_dim=8, linear_value_dim=16,
    linear_conv=4, linear_neg_eigval=True,
)
LATENT_SIZES = dict(
    n_layer=3, n_head=4, n_embd=64, vocab_size=512, block_size=192,
    latent_q=24, latent_kv=16, latent_nope=8, latent_rope=4, latent_v=8,
    dense_layers=1, mlp_hidden=96, experts=8, experts_per_token=2,
    expert_hidden=16, expert_scale=2.5, shared_experts=1, norm_eps=1e-6,
    rope_base=32e6,
)
SSM_SIZES = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "vocab_size": 256, "num_hidden_layers": 2,
    "attn_layer_offset": 1, "attn_layer_period": 2, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "rms_norm_eps": 1e-6,
    "n_layer": 2, "layer_types": ["mamba", "full_attention"],
    "n_head": 4, "n_kv_head": 1, "head_width": 8, "n_embd": 32,
    "block_size": 128, "mlp": "swiglu", "mlp_hidden": 64, "qk_norm": False,
    "rope_style": "none", "norm_scale": True, "norm_eps": 1e-6,
    "tie_embeddings": True, "attn_impl": "naive", "remat": "none",
}


@pytest.fixture(scope="module")
def dense():
    return GPT.init(jax.random.PRNGKey(0), DENSE)


def _model(kind, dense):
    if kind == "dense":
        return dense
    if kind == "gated_delta":
        cfg = ModelConfig(
            mlp="swiglu", qk_norm=True, qk_norm_kind="rms_full",
            rope_style="none", norm_scale=True, norm_order="post",
            tie_embeddings=False, attn_impl="naive", remat="none",
            **GDN_SIZES,
        )
        w = weights_hybrid.make(jax.random.PRNGKey(3), GDN_SIZES, jnp.float32)
        return serve_hybrid.fill_model(w, cfg)
    if kind == "latent":
        cfg = ModelConfig(
            attention="latent", mlp="experts", expert_scoring="sigmoid",
            expert_bias=True, qk_norm=False, norm_scale=True,
            tie_embeddings=False, remat="none", **LATENT_SIZES,
        )
        w = weights_latent.make(
            jax.random.PRNGKey(3), LATENT_SIZES, jnp.float32)
        return serve_latent.fill_model(w, cfg)
    w = weights_ssm.make(jax.random.PRNGKey(5), SSM_SIZES, jnp.float32)
    return serve_ssm.fill_model(w, program.model_config(SSM_SIZES))


def _engine(model, ahead=True, **kw):
    kw = {"slots": 3, "page_size": 8, "num_pages": 96, "window": W,
          "prefill_chunk": 16, "cache_dtype": jnp.float32,
          "paged_kernel": "xla", **kw}
    eng = ServingEngine(model, **kw)
    eng._dispatch_ahead = ahead
    return eng


def _prompt(i, n, vocab):
    return np.random.default_rng(i).integers(0, vocab - 1, n).astype(np.int32)


def _closed_loop(eng, reqs, clients):
    """Each of ``clients`` sends its next request of ``reqs`` (prompt, new,
    submit kwargs) when its last one finished: tokens per request, in order."""
    vocab = eng.model.config.vocab_size
    todo = list(enumerate(reqs))
    rids = {}

    def send():
        i, (n, new, kw) = todo.pop(0)
        rids[i] = eng.submit(_prompt(i, n, vocab), new, **kw)

    for _ in range(min(clients, len(todo))):
        send()
    while eng.has_work:
        before = len(eng.finished)
        eng.step()
        for _ in range(len(eng.finished) - before):
            if todo:
                send()
    return [eng.finished[rids[i]].tokens for i in range(len(reqs))]


def _pair(model, reqs, clients, **kw):
    """The same closed loop with windows ahead and in the plain order."""
    out = []
    for ahead in (True, False):
        eng = _engine(model, ahead, **kw)
        out.append((_closed_loop(eng, reqs, clients), eng))
    return out


@pytest.mark.parametrize("kind,temperature", [
    ("dense", 0.0), ("dense", 0.8), ("gated_delta", 0.0), ("mamba", 0.0),
    ("latent", 0.0),
])
def test_windows_ahead_serve_the_plain_order(dense, kind, temperature):
    """Fixed-length outputs in a full closed loop with nobody waiting: most
    windows go ahead, and every request's tokens and the engine's counters
    are the plain order's. Sampled, the key stream is (seed, emitted), so
    the two agree token for token."""
    model = _model(kind, dense)
    reqs = [(5 + 2 * i, 6 * W, {"seed": i}) for i in range(6)]
    (got, eng), (want, plain) = _pair(
        model, reqs, clients=3, temperature=temperature,
    )
    assert got == want
    st, ref = eng.stats(), plain.stats()
    assert st["windows_ahead"] > st["decode_dispatches"] // 2, st
    assert ref["windows_ahead"] == 0
    for k in SAME:
        assert getattr(eng, k) == getattr(plain, k), k
    if kind == "latent":
        assert eng.expert_rows_routed > 0 and eng.experts_touched > 0
    assert st["device_reads"] == st["decode_dispatches"]
    assert eng._flight is None and not eng.has_work
    eng.alloc.check()
    assert eng._window_fn._cache_size() == 1  # the program compiled once


def test_windows_ahead_on_a_mesh_run_the_compiled_program(dense):
    """Tensor-parallel over two devices: the window dispatched ahead takes
    the one in flight's outputs, replicated over the mesh, and the host's
    arrays are put the same way, so every window (ahead or not) runs the
    one program compiled; the tokens are the plain order's."""
    mesh = create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=2),
        devices=jax.devices()[:2],
    )
    reqs = [(5 + 2 * i, 6 * W, {"seed": i}) for i in range(6)]
    (got, eng), (want, plain) = _pair(
        dense, reqs, clients=3, temperature=0.8, mesh=mesh,
    )
    assert got == want
    assert eng.windows_ahead > eng.decode_dispatches // 2
    for e in (eng, plain):
        assert e._window_fn._cache_size() == 1


# each case: requests (prompt, new, kwargs), clients, engine kwargs, and how
# many steps to watch (None: the whole run)
CASES = {
    # every window carries a slot whose budget ends inside it
    "budget_ends_in_window": (
        [(6, W, {}), (9, W - 1, {}), (7, W, {}), (5, 2, {})], 2,
        {"slots": 2}, None),
    # a slot holds no request: one sent before the next step joins the next
    # window in the plain order
    "empty_slot": ([(6, 5 * W, {}), (8, 5 * W, {})], 2, {"slots": 3}, None),
    # a request waits for the one slot until the last, whose budget ends in
    # its first window
    "queued": (
        [(6, 3 * W, {}), (8, 3 * W, {}), (7, W, {})], 3, {"slots": 1}, None),
    # the second slot prefills a granule a step for longer than the first
    # decodes; its own budget ends in its first window
    "prefilling": (
        [(5, 3 * W, {}), (70, W, {})], 2,
        {"slots": 2, "prefill_chunk": 8, "prefill_budget": 8}, None),
    "eos": ([(6, 5 * W, {"eos_id": 3}), (8, 5 * W, {"eos_id": 3})], 2,
            {"slots": 2}, None),
    # the next window's pages would take an eviction: watched until it
    "growth_would_evict": (
        [(8, 3 * W, {}), (8, 3 * W, {})], 2,
        {"slots": 2, "page_size": 4, "num_pages": 7, "prefix_cache": False},
        "evictions"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_window_ahead_where_the_rule_forbids(dense, case):
    reqs, clients, kw, until = CASES[case]
    if until is None:
        (got, eng), (want, _) = _pair(dense, reqs, clients, **kw)
        assert got == want
        assert eng.windows_ahead == 0
        return
    eng = _engine(dense, **kw)
    vocab = DENSE.vocab_size
    for i, (n, new, skw) in enumerate(reqs):
        eng.submit(_prompt(i, n, vocab), new, **skw)
    while not getattr(eng, until):
        assert eng.has_work
        eng.step()
        assert eng.windows_ahead == 0 and eng._flight is None
    (got, _), (want, _) = _pair(dense, reqs, clients, **kw)
    assert got == want


def _in_flight(model, new=5 * W):
    """Two requests of ``new`` tokens with windows ahead, stepped until a
    window is in flight; and the plain order's tokens for the same two."""
    reqs = [(6, new, {"seed": 0}), (9, new, {"seed": 1})]
    eng = _engine(model, slots=2)
    rids = [eng.submit(_prompt(i, n, DENSE.vocab_size), new, **kw)
            for i, (n, new, kw) in enumerate(reqs)]
    while eng._flight is None:
        eng.step()
    want = _closed_loop(_engine(model, False, slots=2), reqs, 2)
    return eng, rids, want


@pytest.mark.parametrize("what", ["cancel", "cancel_ended", "drain", "fault"])
def test_a_window_in_flight_is_read_before_a_slot_changes(dense, what):
    # cancel_ended: the window in flight is the requests' last
    eng, rids, want = _in_flight(dense, 2 * W if what == "cancel_ended"
                                 else 5 * W)
    reads = eng.device_reads
    # what the requests had when the slots changed, and with the window in
    # flight read: W tokens more each, those of the plain order
    had = [len(eng.lookup(r).tokens) for r in rids]
    assert all(had)
    then = [w[: h + W] for h, w in zip(had, want)]
    if what == "cancel":
        assert eng.cancel(rids[0])
        assert eng.device_reads == reads + 1 and eng._flight is None
        assert eng.cancelled[rids[0]].tokens == then[0]
        assert eng.lookup(rids[1]).tokens == then[1]
        eng.alloc.check()
        eng.run()
        assert eng.finished[rids[1]].tokens == want[1]
    elif what == "cancel_ended":
        # the request left as finished, with every token: nothing to cancel
        assert not eng.cancel(rids[0])
        assert eng.device_reads == reads + 1 and eng._flight is None
        assert eng.finished[rids[0]].tokens == then[0] == want[0]
        assert rids[0] not in eng.cancelled
        eng.alloc.check()
        eng.run()
        assert eng.finished[rids[1]].tokens == want[1]
    elif what == "drain":
        out = eng.drain_requests()
        assert eng.device_reads == reads + 1 and eng._flight is None
        assert not eng.has_work and [r.tokens for r in out] == then
        eng.alloc.check()
        # a survivor resumes each drained request where it stood
        fresh = _engine(dense, slots=2)
        new = [fresh.resubmit(r) for r in out]
        fresh.run()
        assert [fresh.finished[r].tokens for r in new] == want
    else:
        eng._fault_hook = FaultPlan.parse(f"{eng.fault_step + 1}:crash").hook()
        with pytest.raises(ReplicaCrash):
            eng.step()
        # the window in flight was read before the fault fired, and nothing
        # was dispatched after it
        assert eng.device_reads == reads + 1 and eng._flight is None
        assert eng.decode_dispatches == eng.device_reads
        eng.alloc.check()
        for r, w in zip(rids, want):
            toks = eng.lookup(r).tokens
            assert toks == w[: len(toks)]
        eng.run()  # the hook fires once: the engine drains
        assert [eng.finished[r].tokens for r in rids] == want

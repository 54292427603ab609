"""TPxDP sharded paged serving (midgpt_tpu.serving on a multi-chip mesh):
greedy token-identity of the tensor-parallel engine against the
single-chip engine across the serving feature matrix (prefix cache x
chunked prefill x speculation x eviction x int8 quant), program-cache
distinctness per mesh, the shared-nothing DP cluster's
replica-placement invariance, and the
no-batch-allgather-in-page-gather audit rule (canned-HLO fixtures +
the live sharded program audits).

The exactness chain: test_serving.py pins the single-chip engine to the
exact fixed-batch sampler; these tests pin the sharded engine to the
single-chip engine. Sharding only reorders the two row-parallel
reductions per layer (wo / w_down psums), so identity is a seeded
contract, same regime as every serving PR's greedy-identity matrix —
f32 cache dtype keeps the argmax margins wide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import MeshConfig, ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.parallel.mesh import create_mesh
from midgpt_tpu.serving import (
    ServingCluster,
    ServingEngine,
    pages_needed,
    serving_meshes,
)
from midgpt_tpu.serving.engine import (
    _PROGRAM_CACHE,
    _mesh_key,
    make_decode_window,
)

# n_head=4 (MHA) and vocab 96 divide tp=2 and tp=4; same family as the
# test_serving.py model so failures triangulate
CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)


@pytest.fixture(scope="module")
def model():
    return GPT.init(jax.random.PRNGKey(0), CFG)


def _mesh(tp):
    return create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=tp),
        devices=jax.devices()[:tp],
    )


def _prompts(n, base_len=5, stride=3):
    return [
        np.asarray(
            jax.random.randint(
                jax.random.PRNGKey(100 + i), (base_len + stride * i,), 0,
                CFG.vocab_size,
            )
        )
        for i in range(n)
    ]


def _run(model, mesh, prompts, n_new, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("window", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    eng = ServingEngine(model, mesh=mesh, **kw)
    rids = [eng.submit(p, n_new, seed=i) for i, p in enumerate(prompts)]
    finished = eng.run()
    return [finished[r].tokens for r in rids], eng


# the feature matrix both tp degrees run against the single-chip engine:
# (prefix_cache, prefill_chunk, speculate, quant). The fast tier covers
# every FEATURE on tp=2 plus the tp=4 baseline; the remaining
# geometry x feature cross-products ride the slow tier (the CI
# serving-audit job runs this file unfiltered) — each slow combo is a
# fresh sharded-program compile, the most expensive thing in the file.
MATRIX = [
    pytest.param(True, None, 0, None, id="cache"),
    pytest.param(False, None, 0, None, id="nocache"),
    pytest.param(True, None, 3, None, id="spec"),
    pytest.param(True, None, 3, "int8", id="spec-quant"),
]
MATRIX_SLOW = [
    pytest.param(True, 3, 0, None, id="chunked"),
    pytest.param(True, 3, 0, "int8", id="chunked-quant"),
]


@pytest.fixture(scope="module")
def matrix_refs(model):
    """Single-chip engine streams per matrix combo, computed LAZILY and
    memoized for the module: the sharded runs all compare against
    these, and the fast tier must not pay for slow-only combos."""
    prompts = _prompts(3)
    refs = {}

    def get(cache, chunk, spec, quant):
        key = (cache, chunk, spec, quant)
        if key not in refs:
            refs[key], _ = _run(
                model, None, prompts, 10, prefix_cache=cache,
                prefill_chunk=chunk, speculate=spec, quant=quant,
            )
        return refs[key]

    return prompts, get


def _assert_tp_identity(model, matrix_refs, tp, cache, chunk, spec, quant):
    prompts, ref = matrix_refs
    got, eng = _run(
        model, _mesh(tp), prompts, 10, prefix_cache=cache,
        prefill_chunk=chunk, speculate=spec, quant=quant,
    )
    assert got == ref(cache, chunk, spec, quant)
    assert eng.tp == tp
    if spec:
        assert eng.verify_dispatches > 0


@pytest.mark.parametrize("cache,chunk,spec,quant", MATRIX)
def test_tp2_token_identity_matrix(model, matrix_refs, cache, chunk, spec,
                                   quant):
    """tp=2 engine is greedy token-identical to the single-chip engine
    across cache on/off x chunked x speculation x quant=int8 — sharding
    splits the weights/KV per chip, never the token stream."""
    _assert_tp_identity(model, matrix_refs, 2, cache, chunk, spec, quant)


@pytest.mark.parametrize("cache,chunk,spec,quant", [MATRIX[0]])
def test_tp4_token_identity(model, matrix_refs, cache, chunk, spec, quant):
    """tp=4 (one KV head per shard — the SNIPPETS.md target geometry)
    stays token-identical on the baseline combo; the rest of the tp=4
    matrix is the slow-tier cross-product below."""
    _assert_tp_identity(model, matrix_refs, 4, cache, chunk, spec, quant)


@pytest.mark.slow
@pytest.mark.parametrize("cache,chunk,spec,quant", MATRIX_SLOW)
def test_tp2_token_identity_matrix_slow(model, matrix_refs, cache, chunk,
                                        spec, quant):
    _assert_tp_identity(model, matrix_refs, 2, cache, chunk, spec, quant)


@pytest.mark.slow
@pytest.mark.parametrize(
    "cache,chunk,spec,quant", MATRIX[1:] + MATRIX_SLOW
)
def test_tp4_token_identity_matrix_slow(model, matrix_refs, cache, chunk,
                                        spec, quant):
    """The full tp=4 feature cross-product (nocache / chunked / spec /
    quant combos) — compile-heavy, slow tier, unfiltered in the CI
    serving-audit job."""
    _assert_tp_identity(model, matrix_refs, 4, cache, chunk, spec, quant)


def test_tp2_eviction_readmission_identity(model):
    """Mid-run eviction + re-admission under page pressure on the
    sharded engine: same evictions, same streams as single-chip (the
    evicted request re-prefills through its own cached pages on both)."""
    prompts = _prompts(4, base_len=6, stride=0)
    ref, re_ = _run(model, None, prompts, 16, num_pages=5, page_size=8)
    got, ge = _run(model, _mesh(2), prompts, 16, num_pages=5, page_size=8)
    assert re_.evictions > 0, "trace must actually exercise eviction"
    assert ge.evictions == re_.evictions
    assert got == ref


def test_tp2_sampled_spec_token_identity(model):
    """Sampled speculation (temperature > 0: rejection-sampling verify)
    under tp=2: the spec-on sampled stream is bitwise the single-chip
    engine's on a repetitive-prompt trace (drafts, acceptance uniforms
    and residual resamples are all functions of (request seed, stream
    position) only — the mesh must not enter the stream). Same seeded-
    contract regime as the greedy matrix: sharding only reorders the
    two row-parallel psums, and the f32 cache keeps the sampled
    compare margins wide."""
    prompts = [
        np.tile(
            np.asarray(
                jax.random.randint(
                    jax.random.PRNGKey(700 + i), (4,), 0, CFG.vocab_size
                )
            ),
            6,
        )
        for i in range(3)
    ]
    kw = dict(temperature=0.8, top_k=20, speculate=3, seed=3)
    ref, re_ = _run(model, None, prompts, 10, **kw)
    got, ge = _run(model, _mesh(2), prompts, 10, **kw)
    assert got == ref
    assert ge.spec_drafted > 0, "repetitive trace must actually draft"
    assert ge.spec_drafted == re_.spec_drafted


def test_engine_rejects_unservable_meshes(model):
    """Serving meshes are tensor-only: sequence/pipeline axes and tp
    degrees that break whole-head or vocab divisibility are refused at
    construction, not at first dispatch."""
    seq_mesh = create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=2, tensor=1),
        devices=jax.devices()[:2],
    )
    with pytest.raises(AssertionError, match="sequence"):
        ServingEngine(model, mesh=seq_mesh)
    with pytest.raises(AssertionError, match="divide heads"):
        ServingEngine(model, mesh=_mesh(8))  # n_head=4 < 8


# ---------------------------------------------------------------------------
# program cache: one compiled program per mesh geometry/placement
# ---------------------------------------------------------------------------


def test_program_cache_distinct_entries_per_mesh(model):
    """A tp=2 engine must never reuse a tp=1 compiled program: the cache
    key carries the mesh axis sizes AND device ids, so None / tp=2 /
    tp=4 / same-geometry-different-devices all get distinct entries,
    while an equal mesh (same shape, same devices) is a cache HIT."""
    pmax = pages_needed(CFG.block_size, 16)
    # slots=2/window=4 matches the geometry every other test in this
    # module compiles, so the tp2/tp4 lookups here are cache HITS — the
    # only fresh compile is the disjoint-devices replica mesh
    mk = lambda mesh: make_decode_window(  # noqa: E731
        model, slots=2, window=4, pmax=pmax, rope_len=CFG.block_size,
        mesh=mesh,
    )
    fn_none = mk(None)
    fn_tp2 = mk(_mesh(2))
    fn_tp4 = mk(_mesh(4))
    # same geometry, disjoint devices (two DP replicas' meshes)
    m_a = create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=2),
        devices=jax.devices()[:2],
    )
    m_b = create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=2),
        devices=jax.devices()[2:4],
    )
    fn_a, fn_b = mk(m_a), mk(m_b)
    fns = [fn_none, fn_tp2, fn_tp4, fn_b]
    assert len({id(f) for f in fns}) == 4, "programs must not be shared"
    assert fn_a is fn_tp2, "equal mesh (shape + devices) must cache-hit"
    # the cache holds one entry per mesh fingerprint at this geometry
    # (earlier tests in this module may already have populated them —
    # that reuse is exactly what the cache exists for)
    dw_fingerprints = {
        k[-1] for k in _PROGRAM_CACHE
        if k[0] == "decode_window" and k[2:4] == (2, 4) and k[1] == CFG
    }
    assert len(dw_fingerprints) >= 4
    assert _mesh_key(m_a) == _mesh_key(_mesh(2))
    assert _mesh_key(m_a) != _mesh_key(m_b)
    assert _mesh_key(None) is None


# ---------------------------------------------------------------------------
# shared-nothing DP cluster
# ---------------------------------------------------------------------------


def test_cluster_streams_are_replica_placement_invariant(model):
    """The same trace through 1, 2, and 3 replicas (and through a TPxDP
    cluster of tp=2 replicas) yields bit-identical per-request streams:
    a request's tokens are a function of the request alone, so admission
    placement is a latency decision, never a correctness one."""
    prompts = _prompts(6, base_len=5, stride=2)
    kw = dict(slots=2, window=4, cache_dtype=jnp.float32)
    eng = ServingEngine(model, **kw)
    rids = [eng.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    fin = eng.run()
    ref = [fin[r].tokens for r in rids]

    for replicas in (2, 3):
        cl = ServingCluster(model, replicas=replicas, **kw)
        crids = [cl.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
        got = cl.run()
        assert [got[r].tokens for r in crids] == ref, replicas
        # least-loaded admission actually spread the trace
        assert all(len(e.finished) > 0 for e in cl.engines)

    meshes = serving_meshes(tp_size=2, dp_replicas=2)
    assert len(meshes) == 2
    assert [_mesh_key(m) is not None for m in meshes] == [True, True]
    assert _mesh_key(meshes[0]) != _mesh_key(meshes[1]), "disjoint devices"
    cl = ServingCluster(model, meshes=meshes, **kw)
    crids = [cl.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    got = cl.run()
    assert [got[r].tokens for r in crids] == ref


def test_cluster_least_loaded_admission_and_stats(model):
    """Admission routes to the smallest backlog (lowest index on ties)
    and the aggregated stats sum the replica counters."""
    cl = ServingCluster(
        model, replicas=2, slots=1, window=4, cache_dtype=jnp.float32
    )
    prompts = _prompts(4, base_len=4, stride=1)
    for i, p in enumerate(prompts):
        cl.submit(p, 6, seed=i)
    # round-robin under equal load: 0, 1, 0, 1
    assert [len(e.queue) for e in cl.engines] == [2, 2]
    finished = cl.run()
    assert len(finished) == 4
    st = cl.stats()
    assert st["dp_replicas"] == 2
    assert st["tokens_generated"] == 4 * 6
    assert st["tokens_generated"] == sum(
        s["tokens_generated"] for s in st["per_replica"]
    )
    assert len(st["per_replica"]) == 2


# ---------------------------------------------------------------------------
# the no-batch-allgather-in-page-gather rule
# ---------------------------------------------------------------------------


def test_page_gather_allgather_rule_on_fixtures():
    """Rule semantics on canned HLO (jax-free, like the other rule
    units): a collective-free sharded program passes; fault injections —
    a pool-payload all-gather, a slot-batch all-gather — fail; the tiny
    argmax-combiner all-gather ([S, tp] float) and integer block-table
    gathers stay legal."""
    from midgpt_tpu.analysis.hlo import MeshInfo
    from midgpt_tpu.analysis.rules import (
        NoPageGatherAllGather,
        StepAnalysis,
    )

    mesh = MeshInfo(axis_names=("tensor",), axis_sizes=(2,))
    payload = {(2, 8, 6, 64, 8), (8, 6, 64, 8), (4, 8, 6, 64, 8),
               (4, 6, 64, 64), (2, 4, 6, 4, 64), (4, 6, 4, 64)}
    rule = NoPageGatherAllGather(payload, slots=4)

    def analyze(hlo):
        return rule.check(StepAnalysis.from_text(hlo, mesh))

    clean = """HloModule m
ENTRY %main (p0: bf16[2,8,3,64,8]) -> bf16[4,96] {
  %ar = f32[4,96]{1,0} all-reduce(f32[4,96]{1,0} %x), replica_groups={{0,1}}
  %ag0 = f32[4,2]{1,0} all-gather(f32[4,1]{1,0} %m), dimensions={1}, replica_groups={{0,1}}
  %ag1 = s32[4,8]{1,0} all-gather(s32[4,4]{1,0} %bt), dimensions={1}, replica_groups={{0,1}}
}
"""
    assert analyze(clean) == []
    pool_gather = clean + (
        "  %bad = bf16[4,6,64,64]{3,2,1,0} all-gather("
        "bf16[4,3,64,64]{3,2,1,0} %ck), dimensions={1}, "
        "replica_groups={{0,1}}\n"
    )
    found = analyze(pool_gather)
    assert len(found) == 1 and "pool-payload" in found[0].message
    batch_gather = clean + (
        "  %bad = f32[4,5,512]{2,1,0} all-gather(f32[2,5,512]{2,1,0} %h), "
        "dimensions={0}, replica_groups={{0,1}}\n"
    )
    found = analyze(batch_gather)
    assert len(found) == 1 and "slot/batch-dim" in found[0].message


@pytest.mark.slow
def test_sharded_serving_audits_pass():
    """The LIVE gate on the tp=2 geometry, bf16 AND quant: all three
    sharded serving programs keep donation 3/3, stay host-sync-free,
    stream int8 (quant), and contain no pool/batch all-gather through
    the page gathers. The replica=2 variant additionally proves an
    unused replica axis rides replicated (the serving_logical_rules
    contract — with 'batch' mapped onto it, the partitioner injected
    slot all-gathers, which THIS rule caught when the mesh support was
    first compiled)."""
    from midgpt_tpu.analysis.harness import (
        audit_decode_window,
        audit_prefill_chunk,
        audit_verify_program,
    )
    from midgpt_tpu.config import get_config

    cfg = get_config("shakespeare_char")
    for mesh_shape in ({"tensor": 2}, {"tensor": 2, "replica": 2}):
        for fn, kw in (
            (audit_decode_window, dict(slots=2, window=2, page_size=8)),
            (audit_prefill_chunk, dict(chunk_len=32, page_size=8)),
            (audit_verify_program, dict(slots=2, spec_len=2, page_size=8)),
        ):
            for quant in (False, True):
                analysis, report = fn(
                    cfg, quant=quant, mesh_shape=mesh_shape, **kw
                )
                assert report.ok, (mesh_shape, quant, report.violations)
                assert any(
                    r.rule == "no-batch-allgather-in-page-gather"
                    for r in report.results
                )
                assert len(
                    {e.param_number for e in analysis.aliases}
                ) >= 3, "pool + logits donation must survive sharding"

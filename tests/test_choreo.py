"""Arithmetic-choreography prover tests (analysis/choreo.py).

The prover must (a) PASS on the shipped tree — decode window, prefill
chunk and verify program satisfy their documented dtype-choreography
contracts — and (b) FAIL on both historical bug classes, injected as
faulty attention variants:

- the PR 4 bug: a chunk-prefill variant that upcasts to f32 before the
  score einsums and keeps f32 probs through the PV contraction (the
  "cast-early" drift that flipped near-tied greedy argmaxes on a real
  checkpoint);
- the PR 5 bug: a verify variant that reuses the PREFILL choreography
  (bf16 score einsums, ``* scale``, probs rounded to the value dtype)
  instead of mirroring the decode window's arithmetic.

The faulty variants below copy the real methods' structure with exactly
the historical arithmetic flipped, and are monkeypatched onto
``Attention`` so the prover traces them through the REAL program
factories — the same route a regression would take.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from midgpt_tpu.analysis.choreo import (
    attention_regions,
    extract_choreography,
    flatten_jaxpr,
    normalized_trace,
)
from midgpt_tpu.analysis.harness import prove_serving_choreography
from midgpt_tpu.models.gpt import Attention
from midgpt_tpu.parallel.sharding import shard_act
from midgpt_tpu.serving import engine as engine_mod


@pytest.fixture(scope="module")
def healthy_report():
    return prove_serving_choreography("openwebtext")


def _checks(report):
    return {c.name: c.ok for c in report.checks}


# ---------------------------------------------------------------------------
# the prover passes on the shipped tree
# ---------------------------------------------------------------------------


def test_prover_passes_on_current_tree(healthy_report):
    assert healthy_report.ok, "\n".join(
        f"{c.name}: {c.detail}"
        for c in healthy_report.checks
        if not c.ok
    )


def test_prover_passes_on_quant_path():
    rep = prove_serving_choreography("openwebtext", quant=True)
    assert rep.ok, "\n".join(
        f"{c.name}: {c.detail}" for c in rep.checks if not c.ok
    )
    # the quantized lm head must carry the dequant epilogue in ALL
    # three programs (a missing epilogue = wrong logits, an epilogue on
    # some programs only = choreography drift)
    for p in rep.programs:
        if p.name != "naive_reference":
            assert p.lm_head_epilogue, p.name


def test_decode_and_verify_traces_are_op_identical(healthy_report):
    progs = {p.name: p for p in healthy_report.programs}
    assert progs["decode_window"].attention == progs["verify"].attention
    # and the documented ASYMMETRY is real: the prefill chunk's probs
    # round to the value dtype (naive contract) while decode keeps f32
    assert progs["decode_window"].softmax.probs_dtype == {"float32"}
    assert progs["prefill_chunk"].softmax.probs_dtype == {"bfloat16"}


def test_report_serializes(healthy_report):
    d = healthy_report.to_dict()
    assert d["ok"] is True
    assert set(d["programs"]) == {
        "decode_window", "prefill_chunk", "verify", "naive_reference"
    }


# ---------------------------------------------------------------------------
# flattener units
# ---------------------------------------------------------------------------


def test_flatten_tracks_invar_origin_through_structural_ops():
    def f(w, x):
        # weight sliced + cast (the stacked-layer pattern) then matmul
        wl = jnp.transpose(w[0]).astype(jnp.bfloat16)
        return x @ wl

    g = flatten_jaxpr(
        jax.make_jaxpr(f)(
            jnp.zeros((2, 4, 8)), jnp.zeros((3, 8), jnp.bfloat16)
        )
    )
    dots = [op for op in g.ops if op.prim == "dot_general"]
    assert len(dots) == 1
    assert "invar" in dots[0].in_origins


def test_flatten_recurses_into_jitted_calls():
    @jax.jit
    def inner(x):
        return jax.nn.softmax(x)

    def f(x):
        return inner(x * 2.0)

    g = flatten_jaxpr(jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32)))
    prims = {op.prim for op in g.ops}
    assert "exp" in prims and "reduce_max" in prims


def test_attention_regions_one_per_layer(healthy_report):
    for p in healthy_report.programs:
        if p.name == "naive_reference":
            continue
        assert p.n_layers == 2  # the choreography-size trace depth


def test_normalized_trace_drops_structure_keeps_dtypes():
    def f(x):
        y = jnp.transpose(x).reshape(-1)
        return jnp.exp(y.astype(jnp.float32))

    g = flatten_jaxpr(jax.make_jaxpr(f)(jnp.zeros((2, 3), jnp.bfloat16)))
    trace = normalized_trace(g)
    assert trace == [
        ("convert_element_type", ("bfloat16",), ("float32",)),
        ("exp", ("float32",), ("float32",)),
    ]


# ---------------------------------------------------------------------------
# fault injection: the PR 4 bug (cast-early prefill chunk)
# ---------------------------------------------------------------------------


def _cast_early_prefill_paged_at(
    self, x, pool_k, pool_v, bt, layer, mask_pool, mask_self,
    sin_rows, cos_rows, **_new_kwargs,
):
    """prefill_paged_at with the HISTORICAL PR 4 drift re-injected:
    f32 upcast before the score einsums and f32 probs through the PV
    contraction (instead of mirroring naive_attention's bf16-operand /
    f32-accumulate scores and value-dtype probs)."""
    from midgpt_tpu.models.layers import apply_rotary

    b, t, d = x.shape
    h, hkv = self.n_head, self.n_kv_head
    c = d // h
    qkv = self.wqkv(x)
    q = qkv[..., : h * c].reshape(b, t, h, c)
    k = qkv[..., h * c : (h + hkv) * c].reshape(b, t, hkv, c)
    v = qkv[..., (h + hkv) * c :].reshape(b, t, hkv, c)
    if self.q_norm is not None:
        q = self.q_norm(q)
        k = self.k_norm(k)
    q = jnp.transpose(q, (0, 2, 1, 3))
    k = jnp.transpose(k, (0, 2, 1, 3))
    v = jnp.transpose(v, (0, 2, 1, 3))
    q = apply_rotary(q, sin_rows, cos_rows)
    k = apply_rotary(k, sin_rows, cos_rows)
    pk_l = jnp.take(pool_k[layer], bt, axis=0, mode="clip")
    pv_l = jnp.take(pool_v[layer], bt, axis=0, mode="clip")
    _, pmax, ps, _ = pk_l.shape
    ck = jnp.transpose(pk_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    cv = jnp.transpose(pv_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    qg = q.reshape(b, hkv, h // hkv, t, c)
    # THE BUG: cast-early scores (f32 multiply operands)
    s_pool = jnp.einsum(
        "bhgtc,bhcw->bhgtw",
        qg.astype(jnp.float32), ck.astype(jnp.float32),
    )
    s_self = jnp.einsum(
        "bhgtc,bhsc->bhgts",
        qg.astype(jnp.float32), k.astype(jnp.float32),
    )
    s_all = jnp.concatenate(
        [s_pool + mask_pool, s_self + mask_self], axis=-1
    )
    scale = 1.0 / jnp.sqrt(c).astype(jnp.float32)
    probs = jax.nn.softmax(s_all * scale, axis=-1)
    # THE BUG (cont.): f32 probs straight into the PV contraction
    p_pool = probs[..., : s_pool.shape[-1]]
    p_self = probs[..., s_pool.shape[-1]:]
    o_pool = jnp.einsum(
        "bhgtw,bhcw->bhgtc", p_pool, cv.astype(jnp.float32)
    )
    o_self = jnp.einsum(
        "bhgts,bhsc->bhgtc", p_self, v.astype(jnp.float32)
    )
    out = (o_pool + o_self).reshape(b, h, t, c)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * c)
    out = shard_act(out, None, None, "heads")
    return self.wo(out.astype(x.dtype)), k, v


def test_prover_catches_cast_early_prefill(monkeypatch):
    engine_mod._PROGRAM_CACHE.clear()
    monkeypatch.setattr(
        Attention, "prefill_paged_at", _cast_early_prefill_paged_at
    )
    try:
        rep = prove_serving_choreography("openwebtext")
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    assert checks["prefill-mirrors-naive"] is False
    # the decode/verify contract is untouched by a prefill fault
    assert checks["verify-mirrors-decode"] is True


# ---------------------------------------------------------------------------
# fault injection: the PR 5 bug (prefill-choreography verify)
# ---------------------------------------------------------------------------


def _prefill_flavored_verify_paged_at(
    self, x, pool_k, pool_v, bt, layer, mask_pool, mask_self,
    sin_rows, cos_rows, **_new_kwargs,
):
    """verify_paged_at as PR 5's FIRST CUT wrote it: the prefill
    chunk's choreography (bf16 score einsums with f32 accumulation,
    ``* scale``, probs rounded to the value dtype, no cache-dtype
    rounding of the in-dispatch self K/V) instead of the decode
    window's. Flips near-tied acceptance argmaxes on bf16 checkpoints."""
    from midgpt_tpu.models.layers import apply_rotary

    b, t, d = x.shape
    h, hkv = self.n_head, self.n_kv_head
    c = d // h
    qkv = self.wqkv(x)
    q = qkv[..., : h * c].reshape(b, t, h, c)
    k = qkv[..., h * c : (h + hkv) * c].reshape(b, t, hkv, c)
    v = qkv[..., (h + hkv) * c :].reshape(b, t, hkv, c)
    if self.q_norm is not None:
        q = self.q_norm(q)
        k = self.k_norm(k)
    q = jnp.transpose(q, (0, 2, 1, 3))
    k = jnp.transpose(k, (0, 2, 1, 3))
    v = jnp.transpose(v, (0, 2, 1, 3))
    q = apply_rotary(q, sin_rows, cos_rows)
    k = apply_rotary(k, sin_rows, cos_rows)
    pk_l = jnp.take(pool_k[layer], bt, axis=0, mode="clip")
    pv_l = jnp.take(pool_v[layer], bt, axis=0, mode="clip")
    _, pmax, ps, _ = pk_l.shape
    ck = jnp.transpose(pk_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    cv = jnp.transpose(pv_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    qg = q.reshape(b, hkv, h // hkv, t, c)
    # THE BUG: prefill-flavored scores (compute-dtype operands, f32
    # accumulate) instead of the decode window's f32-upcast VPU form
    s_pool = jnp.einsum(
        "bhgtc,bhcw->bhgtw", qg, ck.astype(qg.dtype),
        preferred_element_type=jnp.float32,
    )
    s_self = jnp.einsum(
        "bhgtc,bhsc->bhgts", qg, k,
        preferred_element_type=jnp.float32,
    )
    s_all = jnp.concatenate(
        [s_pool + mask_pool, s_self + mask_self], axis=-1
    )
    scale = 1.0 / jnp.sqrt(c).astype(jnp.float32)
    probs = jax.nn.softmax(s_all * scale, axis=-1)
    # THE BUG (cont.): probs rounded to the value dtype before PV
    probs = probs.astype(v.dtype)
    p_pool = probs[..., : s_pool.shape[-1]]
    p_self = probs[..., s_pool.shape[-1]:]
    o_pool = jnp.einsum("bhgtw,bhcw->bhgtc", p_pool, cv.astype(v.dtype))
    o_self = jnp.einsum("bhgts,bhsc->bhgtc", p_self, v)
    out = (o_pool + o_self).reshape(b, h, t, c)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * c)
    out = shard_act(out, None, None, "heads")
    return self.wo(out.astype(x.dtype)), k, v


def test_prover_catches_prefill_flavored_verify(monkeypatch):
    engine_mod._PROGRAM_CACHE.clear()
    monkeypatch.setattr(
        Attention, "verify_paged_at", _prefill_flavored_verify_paged_at
    )
    try:
        rep = prove_serving_choreography("openwebtext")
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    assert checks["verify-mirrors-decode"] is False
    # the prefill/naive contract is untouched by a verify fault
    assert checks["prefill-mirrors-naive"] is True


# ---------------------------------------------------------------------------
# fault injection: scale applied before the mask (ordering drift)
# ---------------------------------------------------------------------------


def _scale_before_mask_decode_paged_at(
    self, x, pool_k, pool_v, bt, rk, rv, layer, r, mask_pool, mask_rec,
    sin_rows, cos_rows, **_new_kwargs,
):
    """decode_paged_at with the softmax argument order flipped: scores
    are scaled BEFORE the additive mask lands, so the -inf mask is
    divided too — a drift the shared-arithmetic check must flag even
    though decode and verify would still agree with each other if both
    drifted (which they don't here: only decode is patched, so the
    op-for-op check fires first; the dedicated ordering check is what
    fires when BOTH paths drift together)."""
    b, one, d = x.shape
    h, hkv = self.n_head, self.n_kv_head
    c = d // h
    q, k, v = self._decode_qkv(x, sin_rows, cos_rows)
    zero = jnp.zeros((), r.dtype)
    at = (jnp.asarray(layer, r.dtype), zero, zero, r, zero)
    rk = jax.lax.dynamic_update_slice(rk, k.astype(rk.dtype)[None], at)
    rv = jax.lax.dynamic_update_slice(rv, v.astype(rv.dtype)[None], at)
    pk_l = jnp.take(pool_k[layer], bt, axis=0, mode="clip")
    pv_l = jnp.take(pool_v[layer], bt, axis=0, mode="clip")
    s_, pmax, ps, _ = pk_l.shape
    ck = jnp.transpose(pk_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    cv = jnp.transpose(pv_l.reshape(b, pmax * ps, hkv, c), (0, 2, 3, 1))
    rkl, rvl = rk[layer], rv[layer]
    qg = q.reshape(b, hkv, h // hkv, 1, c)
    qcw = jnp.transpose(qg, (0, 1, 2, 4, 3))
    s_pool = jnp.sum(
        qcw.astype(jnp.float32) * ck[:, :, None].astype(jnp.float32),
        axis=-2,
    )
    s_rec = jnp.sum(
        qg.astype(jnp.float32) * rkl[:, :, None].astype(jnp.float32),
        axis=-1,
    )
    # THE BUG: scale first, then add the mask
    s_all = jnp.concatenate(
        [
            s_pool / math.sqrt(c) + mask_pool[:, None, None, :],
            s_rec / math.sqrt(c) + mask_rec,
        ],
        axis=-1,
    )
    probs = jax.nn.softmax(s_all, axis=-1)
    p_pool = probs[..., : s_pool.shape[-1]]
    p_rec = probs[..., s_pool.shape[-1]:]
    o_pool = jnp.sum(
        p_pool[:, :, :, None, :] * cv[:, :, None].astype(jnp.float32),
        axis=-1,
    )
    o_rec = jnp.sum(
        p_rec[..., None] * rvl[:, :, None].astype(jnp.float32), axis=-2
    )
    out = (o_pool + o_rec).astype(x.dtype)
    out = out.reshape(b, h, 1, c)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, 1, h * c)
    return self.wo(out), rk, rv


def test_prover_catches_scale_before_mask(monkeypatch):
    engine_mod._PROGRAM_CACHE.clear()
    monkeypatch.setattr(
        Attention, "decode_paged_at", _scale_before_mask_decode_paged_at
    )
    try:
        rep = prove_serving_choreography("openwebtext")
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    # the patched decode drifts away from the (unpatched) verify, and
    # the ordering invariant itself fires
    assert (
        checks["verify-mirrors-decode"] is False
        or checks[
            "shared: mask is added before the softmax scale everywhere"
        ] is False
    )


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel as a contract node (PR 9)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kernel_report():
    return prove_serving_choreography("openwebtext", paged_kernel="pallas")


def test_prover_passes_on_kernel_path(kernel_report):
    assert kernel_report.ok, "\n".join(
        f"{c.name}: {c.detail}"
        for c in kernel_report.checks
        if not c.ok
    )
    progs = {p.name: p for p in kernel_report.programs}
    # decode and verify run INSIDE the kernel; the prefill chunk stays
    # on the XLA einsum path (compute-bound, naive-contract)
    assert progs["decode_window"].kernelized
    assert progs["verify"].kernelized
    assert not progs["prefill_chunk"].kernelized


def test_kernel_node_is_one_record_and_bodies_match_decode_contract(
    kernel_report,
):
    """The kernel appears as a single 'paged_kernel' contract node in
    the attention traces (not as inlined internals), decode == verify
    op for op across it, and the KERNEL BODY's softmax signature — ONE
    body, whatever the mask kind — shares the XLA decode window's
    arithmetic: f32 accumulation (the products' ``preferred_element_type``),
    mask-before-scale, one f32 softmax, f32 probs into PV. The unit that
    forms the products differs by design: the kernel's are
    ``dot_general``s of bf16 operands (exact in f32), and its PV over a
    bf16 pool is the limb helper's contract node — f32 probabilities in,
    never a bf16 operand."""
    progs = {p.name: p for p in kernel_report.programs}
    dec = progs["decode_window"]
    kinds = [rec[0] for rec in dec.attention]
    assert kinds.count("paged_kernel") == 1
    assert dec.attention == progs["verify"].attention
    assert dec.softmax == progs["verify"].softmax
    xla = prove_serving_choreography("openwebtext")
    xla_dec = {p.name: p for p in xla.programs}["decode_window"]
    assert dec.softmax.arithmetic() == xla_dec.softmax.arithmetic()
    assert {kind for kind, _, _ in dec.softmax.qk_contracts} == {"dot"}
    assert dec.softmax.probs_dtype == {"float32"}
    assert dec.softmax.pv_contracts == {
        ("dot", ("float32", "bfloat16"), "float32")
    }


def test_prover_proves_kv_dequant_contract():
    rep = prove_serving_choreography(
        "openwebtext", kv_quant=True, paged_kernel="pallas"
    )
    assert rep.ok, "\n".join(
        f"{c.name}: {c.detail}" for c in rep.checks if not c.ok
    )
    for p in rep.programs:
        if p.name != "naive_reference":
            assert p.kv_dequant, p.name
    # and the float-pool trace must NOT carry a stray dequant
    rep2 = prove_serving_choreography("openwebtext", paged_kernel="pallas")
    for p in rep2.programs:
        assert not p.kv_dequant, p.name


def test_prover_catches_bf16_accumulating_kernel(paged_hook):
    """Fault injection: a kernel variant whose products accumulate in
    bf16 (SCORE_ACC_DTYPE, ``_mxu``'s ``preferred_element_type``, is the
    kernels' contract point) must turn the prover red — EXACTLY the
    score-accumulation clause: the products' own ``dot_general`` says
    what they sum in, so the signature stays readable and no sibling
    clause hides the fault or goes red with it."""
    engine_mod._PROGRAM_CACHE.clear()
    paged_hook("SCORE_ACC_DTYPE", jnp.bfloat16)
    try:
        rep = prove_serving_choreography(
            "openwebtext", paged_kernel="pallas"
        )
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    assert [name for name, ok in checks.items() if not ok] == [
        "shared: scores accumulate in f32 everywhere"
    ]


_BAND_CLAUSE = "shared: banded PV accumulation runs in pinned ascending-band order"


@pytest.mark.parametrize("kern", ["xla", "pallas"])
def test_prover_proves_banded_fold_order_multiband(kern, paged_hook):
    """Banded-accumulation-order clause (ISSUE 20), on a genuinely
    multi-banded plan: force 2 pages per band so the PV fold has two
    pool-band partials plus the recent/self partial, and the prover
    must extract the pinned ascending offsets (0, 32, 64) — identical
    for decode and verify, on the kernel body AND the banded XLA
    reference — with every clause green."""
    engine_mod._PROGRAM_CACHE.clear()
    paged_hook("_FORCE_BAND_PAGES", 2)
    try:
        rep = prove_serving_choreography("openwebtext", paged_kernel=kern)
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert rep.ok, "\n".join(
        f"{c.name}: {c.detail}" for c in rep.checks if not c.ok
    )
    order = {p.name: p.band_order for p in rep.programs}
    assert order["decode_window"] == order["verify"] == (0, 32, 64)
    # einsum-contracted programs have no fold: exempt by construction
    assert order["prefill_chunk"] is None
    assert order["naive_reference"] is None


def test_prover_catches_descending_band_fold(paged_hook):
    """Fault injection (the ISSUE 20 clause): reverse the band fold —
    banded_fold summing descending instead of the pinned ascending
    order. f32 addition is not associative, so this is a bitwise drift
    no dtype check can see; the prover must fail EXACTLY the band-order
    clause while every sibling clause stays green (kernel == XLA
    survives the flip because BOTH sides fold through banded_fold)."""
    engine_mod._PROGRAM_CACHE.clear()
    paged_hook("_FORCE_BAND_PAGES", 2)
    paged_hook("_BAND_FOLD_ORDER", "descending")
    try:
        rep = prove_serving_choreography(
            "openwebtext", paged_kernel="pallas"
        )
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    assert checks[_BAND_CLAUSE] is False
    for name, ok in checks.items():
        if name != _BAND_CLAUSE:
            assert ok is True, name
    detail = {c.name: c.detail for c in rep.checks}[_BAND_CLAUSE]
    assert "band_order" in detail


# ---------------------------------------------------------------------------
# the sampled-verify prover (temperature > 0): the verify program's
# rejection-sampling arithmetic proven against the decode window's
# sampler, plus the acceptance-compare dtype fault injection
# ---------------------------------------------------------------------------

_SAMPLED_CHECKS = (
    "sampled: verify row-0 sampler mirrors the decode window's "
    "categorical",
    "sampled: acceptance compares run in f32",
    "sampled: residual renormalization runs in f32",
    "sampled: target softmax runs in f32 in the verify sampler",
)


@pytest.fixture(scope="module")
def sampled_report():
    return prove_serving_choreography(
        "openwebtext", temperature=0.8, top_k=20
    )


def test_sampled_prover_passes_on_current_tree(sampled_report):
    assert sampled_report.ok, "\n".join(
        f"{c.name}: {c.detail}"
        for c in sampled_report.checks
        if not c.ok
    )
    checks = _checks(sampled_report)
    for name in _SAMPLED_CHECKS:
        assert checks[name] is True, name


def test_sampled_checks_ride_next_to_the_greedy_contracts(
    healthy_report, sampled_report
):
    """The T>0 report is the greedy report's check set PLUS the four
    sampled clauses — the greedy choreography contracts (verify mirrors
    decode, f32 softmax, mask-before-scale, ...) must keep being proven
    on the sampled programs, and the greedy report must NOT grow
    sampled clauses (there is no sampler to extract at argmax)."""
    greedy = set(_checks(healthy_report))
    sampled = set(_checks(sampled_report))
    assert sampled == greedy | set(_SAMPLED_CHECKS)
    assert not greedy & set(_SAMPLED_CHECKS)


def test_sampled_prover_passes_on_quant_kernel_cell():
    """One production-precision sampled cell (int8 weights + int8 KV +
    Pallas kernel) — the composition the CI matrix proves exhaustively;
    this pins it in the suite so a local regression fails fast."""
    rep = prove_serving_choreography(
        "openwebtext", quant=True, kv_quant=True, paged_kernel="pallas",
        temperature=0.8, top_k=20,
    )
    assert rep.ok, "\n".join(
        f"{c.name}: {c.detail}" for c in rep.checks if not c.ok
    )


def test_sampled_prover_catches_bf16_acceptance_compare(monkeypatch):
    """Fault injection (the ISSUE 17 clause): re-introduce a
    drifted-dtype acceptance compare — the rejection test
    ``u * q <= p`` evaluated in bf16 — and the prover must fail EXACTLY
    the acceptance-compare clause while every sibling sampled clause
    stays green (the fault is in the compare, not in the categorical,
    the residual, or the softmax)."""
    from midgpt_tpu import sampling as sampling_mod

    def bf16_acceptance(u, q_sel, p_sel):
        return (
            u.astype(jnp.bfloat16) * q_sel.astype(jnp.bfloat16)
        ) <= p_sel.astype(jnp.bfloat16)

    engine_mod._PROGRAM_CACHE.clear()
    monkeypatch.setattr(sampling_mod, "acceptance_mask", bf16_acceptance)
    try:
        rep = prove_serving_choreography(
            "openwebtext", temperature=0.8, top_k=20
        )
    finally:
        engine_mod._PROGRAM_CACHE.clear()
    assert not rep.ok
    checks = _checks(rep)
    assert checks["sampled: acceptance compares run in f32"] is False
    for name in _SAMPLED_CHECKS:
        if name != "sampled: acceptance compares run in f32":
            assert checks[name] is True, name
    detail = {c.name: c.detail for c in rep.checks}[
        "sampled: acceptance compares run in f32"
    ]
    assert "bfloat16" in detail

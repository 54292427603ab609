"""Arithmetic-choreography prover for the serving programs.

The two most expensive serving bugs this repo has shipped were DTYPE
CHOREOGRAPHY drift between attention paths that must agree at greedy-
argmax granularity:

- PR 4: a chunk-prefill variant that upcast the pool K/V to f32 before
  the score einsum (and kept f32 probs through the PV contraction)
  drifted ~2 bf16 ulps from the fixed-batch sampler and flipped
  near-tied greedy argmaxes on a real checkpoint;
- PR 5: the first cut of the speculative VERIFY program reused the
  prefill choreography instead of the decode window's, flipping
  near-tied acceptance argmaxes the same way.

Both were only caught by the ``sample.py --serve`` hardware drive. The
contracts live as prose in ``models/gpt.py`` docstrings ("the dtype
choreography deliberately MIRRORS decode_paged_at op for op"); this
module turns them into a machine check: trace each serving program to a
jaxpr, slice out the per-layer attention subgraph and the lm-head
projection, normalize them into an op-and-dtype trace (primitive,
operand dtypes, cast positions, accumulation dtype, softmax arithmetic
order — shapes deliberately dropped, the programs differ in T), and
assert:

1. ``decode == verify`` — the decode window and the verify program
   produce IDENTICAL normalized attention traces, op for op (the PR 5
   contract: acceptance must reproduce the decode path's argmaxes, so
   it must share the decode path's arithmetic).
2. ``prefill == naive`` — the prefill chunk's softmax-core signature
   (operand dtypes at the score contract, mask-add position, scale op,
   softmax dtype, the probs dtype entering the PV contraction) equals
   ``ops.attention.naive_attention``'s (the PR 4 contract: with an
   empty pool part the chunk must be bitwise what the monolithic
   ``model.hidden`` prefill computes).
3. shared arithmetic — all three programs agree on the invariants they
   DO share: scores accumulate in f32, the additive mask lands before
   the softmax scale, softmax runs in f32 with one joint exp per layer,
   and the lm-head projection choreography (operand dtypes + quant
   epilogue) is identical everywhere.

The deliberate asymmetry between (1) and (2) is the point: decode and
prefill legitimately differ (f32 probs through PV vs probs rounded to
the value dtype; ``/ sqrt(c)`` vs ``* (1/sqrt(c))``), which is exactly
why a verify program that drifts toward the prefill flavor is a bug the
full-sequence check catches.

At ``temperature > 0`` the same discipline extends past the attention
stack into the SAMPLER (:func:`extract_sampler_choreography` /
:func:`prove_sampled_choreography`): the verify program's row-0
categorical (softmax -> temperature -> key-derived gumbel argmax) must
mirror the decode window's op for op, the rejection-sampling acceptance
compare ``u * q(t) <= p(t)`` must run in f32 (a bf16 compare flips
near-tie accept/reject decisions the same way the PR 5 bf16 argmax
flipped near-tie acceptance), and the residual renormalization
``max(p - q, 0)`` with its target softmax must run in f32.

Everything here operates on jaxprs (no compilation, no execution) — a
full three-program proof runs in seconds on CPU. jax is imported at
module level; the CLI imports this module only after platform setup
(same discipline as :mod:`~midgpt_tpu.analysis.harness`).
"""

from __future__ import annotations

import dataclasses
import typing as tp

import jax

# ---------------------------------------------------------------------------
# jaxpr flattening with origin tracking
# ---------------------------------------------------------------------------

# ops that forward their (first) operand's ORIGIN unchanged: moving or
# re-viewing a buffer does not change what the value fundamentally is,
# so a model weight sliced out of the stacked [L, ...] leaf and cast to
# the compute dtype still traces back to its entry parameter
_PASSTHRU = frozenset({
    "slice", "squeeze", "reshape", "transpose", "broadcast_in_dim",
    "device_put", "copy", "convert_element_type", "expand_dims",
    "sharding_constraint",
})

# sub-jaxpr-carrying primitives the flattener recurses into with
# operands aligned to the body's invars (the names jax 0.9 emits);
# params are scanned generically for ClosedJaxpr/Jaxpr values, so any
# other call prim degrades to unaligned recursion instead of silently
# dropping a body
_ALIGNED_CALLS = frozenset({
    "jit", "closed_call", "custom_jvp_call", "custom_vjp_call", "scan",
    "while",
})

# jitted helpers the flattener keeps as ONE contract node, like a
# kernel call, instead of inlining: ``ops.paged_attn.pv_limbs`` takes
# f32 probabilities against bf16 values and returns their f32 product
# sums — inside, the probabilities travel as three bf16 limbs whose sum
# is the f32 probability to the bit (tests/test_paged_attn.py proves
# it), so the bf16 operands of its one pass are NOT a rounding of the
# probabilities, and a prover that inlined it would read them as one
_CONTRACT_CALLS = frozenset({"pv_limbs"})

_FLOAT_DTYPES = frozenset({"bfloat16", "float16", "float32", "float64"})

# the arithmetic alphabet a normalized trace keeps; everything else
# (layout ops, comparisons, integer plumbing, scatters/gathers) is
# movement, not arithmetic, and differs legitimately between programs
_ARITH = frozenset({
    "dot_general", "convert_element_type", "add", "sub", "mul", "div",
    "exp", "exp2", "log", "reduce_max", "reduce_sum", "max", "min",
    "neg", "rsqrt", "sqrt", "square", "integer_pow", "tanh", "erf",
    "logistic", "pow",
})


@dataclasses.dataclass(frozen=True)
class Op:
    """One flattened jaxpr equation, with global value ids and origins."""

    idx: int
    prim: str
    in_dtypes: tp.Tuple[str, ...]
    out_dtypes: tp.Tuple[str, ...]
    in_ids: tp.Tuple[int, ...]  # -1 for literals
    out_ids: tp.Tuple[int, ...]
    # per-input provenance: 'invar' (traces to a program entry through
    # pass-through ops only), 'const', 'lit', or 'var' (computed)
    in_origins: tp.Tuple[str, ...]
    # static shape metadata for the few prims where a positional fact
    # IS the contract: a ``slice``'s start_indices (so the banded-
    # accumulation-order extractor can read which probability columns a
    # PV partial consumed). None for everything else.
    meta: tp.Optional[tp.Tuple[int, ...]] = None


class FlatGraph:
    """Flattened jaxpr: linear op list + producer/consumer maps.
    ``kernels`` collects the body jaxprs of any Pallas kernel calls the
    walk encountered (each appears in ``ops`` as one ``paged_kernel``
    CONTRACT NODE rather than as inlined internals — the kernel body is
    proven separately, see :func:`extract_choreography`)."""

    def __init__(self, ops: tp.List[Op],
                 kernels: tp.Optional[tp.List[tp.Any]] = None):
        self.ops = ops
        self.kernels = kernels or []
        self.producer: tp.Dict[int, Op] = {}
        self.consumers: tp.Dict[int, tp.List[Op]] = {}
        for op in ops:
            for vid in op.out_ids:
                self.producer[vid] = op
            for vid in op.in_ids:
                if vid >= 0:
                    self.consumers.setdefault(vid, []).append(op)


def flatten_jaxpr(closed) -> FlatGraph:
    """Flatten a (Closed)Jaxpr into a single linear op list, recursing
    into pjit/scan/while/custom_jvp bodies (each body once — choreography
    is per-iteration-identical by construction of a scan). Value ids are
    global; sub-jaxpr invars inherit the caller operands' ids/origins, so
    an entry parameter keeps its 'invar' origin through any call depth."""
    jaxpr = getattr(closed, "jaxpr", closed)
    ops: tp.List[Op] = []
    next_id = [0]
    # var -> (vid, origin)
    env: tp.Dict[tp.Any, tp.Tuple[int, str]] = {}

    def fresh(origin: str) -> tp.Tuple[int, str]:
        vid = next_id[0]
        next_id[0] += 1
        return (vid, origin)

    for i, v in enumerate(jaxpr.invars):
        env[v] = fresh("invar")
    for v in jaxpr.constvars:
        env[v] = fresh("const")

    def read(env_, atom) -> tp.Tuple[int, str]:
        if hasattr(atom, "val"):  # Literal
            return (-1, "lit")
        if atom not in env_:
            env_[atom] = fresh("var")
        return env_[atom]

    kernels: tp.List[tp.Any] = []

    def walk(jpr, env_) -> None:
        for eqn in jpr.eqns:
            contract = None
            if eqn.primitive.name == "pallas_call":
                # a Pallas kernel is ONE contract node in the outer
                # trace: (operand dtypes, output dtypes). Its body is
                # collected for the separate kernel-choreography proof
                # rather than inlined — the internals are a different
                # alphabet (refs, DMAs) and the contract the outer
                # comparison needs is "same operands in, same dtype
                # arithmetic inside, same dtype out".
                kernels.append(eqn.params.get("jaxpr"))
                contract = "paged_kernel"
            elif (eqn.primitive.name == "jit"
                    and eqn.params.get("name") in _CONTRACT_CALLS):
                # a contraction helper whose exactness its own test
                # proves (see ``_CONTRACT_CALLS``): ONE node, read by
                # the dtypes that cross it
                contract = eqn.params["name"]
            if contract is not None:
                ins = [read(env_, a) for a in eqn.invars]
                rec_outs = []
                for ov in eqn.outvars:
                    vid, _ = fresh("var")
                    env_[ov] = (vid, "var")
                    rec_outs.append(vid)
                ops.append(Op(
                    idx=len(ops),
                    prim=contract,
                    in_dtypes=tuple(
                        str(getattr(a.aval, "dtype", "?"))
                        for a in eqn.invars
                    ),
                    out_dtypes=tuple(
                        str(getattr(v.aval, "dtype", "?"))
                        for v in eqn.outvars
                    ),
                    in_ids=tuple(vid for vid, _ in ins),
                    out_ids=tuple(rec_outs),
                    in_origins=tuple(origin for _, origin in ins),
                ))
                continue
            # include jaxprs nested inside tuple/list params too:
            # lax.cond's 'branches' is a plain TUPLE of ClosedJaxprs,
            # which a bare hasattr over params.values() would skip —
            # arithmetic inside a cond branch would then vanish from
            # the normalized trace (a vacuous pass, the same blind spot
            # class the extraction-degeneracy guard exists for). Tuple
            # params recurse UNALIGNED (fresh origins), the safe
            # degradation the comment above describes.
            subs = [
                c
                for p in eqn.params.values()
                for c in (p if isinstance(p, (tuple, list)) else (p,))
                if hasattr(c, "eqns") or hasattr(c, "jaxpr")
            ]
            nested = [getattr(s, "jaxpr", s) for s in subs]
            if eqn.primitive.name == "cond":
                # the paged kernel's band skip: ``cond(band is live,
                # compute, constant)``. Every branch is walked with its
                # invars aligned to the cond's operands (its arithmetic
                # stays in the trace), and the outputs are the HEAVIEST
                # branch's — the computing one — so the score and PV
                # dataflow runs through the cond instead of ending at
                # it (the constant branch has no arithmetic to lose)
                heavy = max(nested, key=lambda j: len(j.eqns))
                for sub in nested:
                    senv = {
                        iv: read(env_, oa)
                        for iv, oa in zip(sub.invars, eqn.invars[1:])
                    }
                    for cv in sub.constvars:
                        senv[cv] = fresh("const")
                    walk(sub, senv)
                    if sub is heavy:
                        for ov, io in zip(eqn.outvars, sub.outvars):
                            env_[ov] = (
                                senv[io] if io in senv else fresh("var")
                            )
                continue
            if nested:
                aligned = (
                    eqn.primitive.name in _ALIGNED_CALLS
                    and len(nested) == 1
                    and len(nested[0].invars) == len(eqn.invars)
                )
                for sub in nested:
                    senv: tp.Dict[tp.Any, tp.Tuple[int, str]] = {}
                    if aligned:
                        for iv, oa in zip(sub.invars, eqn.invars):
                            senv[iv] = read(env_, oa)
                    else:
                        for iv in sub.invars:
                            senv[iv] = fresh("var")
                    for cv in sub.constvars:
                        senv[cv] = fresh("const")
                    walk(sub, senv)
                    if aligned:
                        for ov, io in zip(eqn.outvars, sub.outvars):
                            env_[ov] = (
                                senv[io]
                                if io in senv
                                else fresh("var")
                            )
                if not aligned:
                    for ov in eqn.outvars:
                        env_[ov] = fresh("var")
                continue
            ins = [read(env_, a) for a in eqn.invars]
            in_d = tuple(
                str(getattr(a.aval, "dtype", "?")) for a in eqn.invars
            )
            out_d = tuple(
                str(getattr(v.aval, "dtype", "?")) for v in eqn.outvars
            )
            nm = eqn.primitive.name
            # every op gets fresh OUT ids (so it appears in the graph),
            # but pass-through ops forward their first operand's ORIGIN
            # — the invariant _dot_kind's 'proj' classification rests on
            out_origin = (
                ins[0][1] if nm in _PASSTHRU and ins else "var"
            )
            rec_outs = []
            for ov in eqn.outvars:
                vid, _ = fresh(out_origin)
                env_[ov] = (vid, out_origin)
                rec_outs.append(vid)
            meta = None
            if nm == "slice":
                si = eqn.params.get("start_indices")
                if si is not None:
                    meta = tuple(int(x) for x in si)
            ops.append(Op(
                idx=len(ops),
                prim=nm,
                in_dtypes=in_d,
                out_dtypes=out_d,
                in_ids=tuple(vid for vid, _ in ins),
                out_ids=tuple(rec_outs),
                in_origins=tuple(origin for _, origin in ins),
                meta=meta,
            ))

    walk(jaxpr, env)
    return FlatGraph(ops, kernels)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

# one record of a normalized trace: (kind, in_dtypes, out_dtypes)
TraceRec = tp.Tuple[str, tp.Tuple[str, ...], tp.Tuple[str, ...]]


def _is_float_op(op: Op) -> bool:
    return bool(
        (set(op.in_dtypes) | set(op.out_dtypes)) & _FLOAT_DTYPES
    )


def _dot_kind(op: Op) -> str:
    """'proj' for a weight matmul (an operand traces to a program entry
    parameter through pass-through ops only — the model pytree is an
    ENTRY PARAMETER of every serving program, PR 6), 'rope' for the
    const rotation-matrix contraction of apply_rotary, 'dot' for a
    data-data contraction (QK scores / PV)."""
    if "invar" in op.in_origins:
        return "proj"
    if "const" in op.in_origins:
        return "rope"
    return "dot"


def _trace_pairs(
    graph: FlatGraph,
) -> tp.List[tp.Tuple[TraceRec, Op]]:
    """(record, op) pairs of the program's float arithmetic in program
    order — the op reference lets region extraction ask structural
    questions (is this exp an attention softmax?) that the record's
    dtypes alone cannot answer."""
    out: tp.List[tp.Tuple[TraceRec, Op]] = []
    for op in graph.ops:
        if op.prim == "paged_kernel":
            # the kernel contract node: float/int8 operand dtypes as a
            # sorted multiset (decode and verify pass the same PAYLOADS
            # — queries, row buffers, pool planes, scales — in different
            # positional orders and with different integer plumbing;
            # the contract is what dtypes cross the kernel boundary)
            kept = tuple(sorted(
                d for d in op.in_dtypes
                if d in _FLOAT_DTYPES or d == "int8"
            ))
            out.append((("paged_kernel", kept, op.out_dtypes), op))
            continue
        if op.prim not in _ARITH or not _is_float_op(op):
            continue
        kind = _dot_kind(op) if op.prim == "dot_general" else op.prim
        out.append(((kind, op.in_dtypes, op.out_dtypes), op))
    return out


def normalized_trace(graph: FlatGraph) -> tp.List[TraceRec]:
    """The program's float arithmetic as (kind, in_dtypes, out_dtypes)
    records in program order — the 'op-and-dtype trace'. Shapes are
    deliberately absent (decode is T=1, verify T=spec+1, a chunk T=N;
    the choreography contract is about dtypes and order, not widths)."""
    return [rec for rec, _ in _trace_pairs(graph)]


def attention_regions(graph: FlatGraph) -> tp.List[tp.List[TraceRec]]:
    """Per-layer normalized ATTENTION traces: the arithmetic between the
    QKV projection and the output projection of each layer, located as
    the inter-'proj' region containing that layer's joint softmax (its
    ``exp``). One region per transformer layer; programs traced at the
    same depth must produce the same number of regions."""
    regions: tp.List[tp.List[TraceRec]] = []
    current: tp.List[TraceRec] = []
    has_exp = False
    for rec, op in _trace_pairs(graph):
        if rec[0] == "proj":
            if has_exp:
                regions.append(current)
            current = []
            has_exp = False
            continue
        current.append(rec)
        if rec[0] == "paged_kernel":
            # a paged_kernel node IS the layer's joint softmax (the exp
            # lives in the kernel body, proven separately)
            has_exp = True
        elif rec[0] == "exp" and _is_attention_exp(graph, op):
            # ONLY an attention softmax flags a region: the sampled
            # verify program's target softmax (sampling.target_probs
            # over the lm-head logits, temperature > 0) also trails an
            # exp, but that is sampler arithmetic with its own prover
            # (prove_sampled_choreography), not an attention layer
            has_exp = True
    if has_exp:  # trailing region (the bare naive_attention reference)
        regions.append(current)
    return regions


@dataclasses.dataclass(frozen=True)
class SoftmaxSignature:
    """The softmax-core dtype choreography of one attention subgraph —
    the facts the PR 4/PR 5 bugs flipped, independent of how many score
    blocks feed the joint softmax (decode: pool+recent; verify:
    pool+self; prefill: pool+self; naive: one)."""

    # canonical score contractions feeding the softmax: each is
    # ('dot' | 'mulsum', multiply operand dtypes, accumulation dtype)
    qk_contracts: tp.FrozenSet[tp.Tuple[str, tp.Tuple[str, ...], str]]
    mask_add_dtypes: tp.FrozenSet[tp.Tuple[str, ...]]  # additive-mask adds
    scale_op: str  # 'div' | 'mul' — the 1/sqrt(C) application
    scale_before_mask: bool  # True = scale applied before the mask add
    softmax_dtype: str  # exp operand/result dtype
    # probability operand dtype entering the PV contraction(s), and the
    # canonical PV contractions themselves
    probs_dtype: tp.FrozenSet[str]
    pv_contracts: tp.FrozenSet[tp.Tuple[str, tp.Tuple[str, ...], str]]

    def arithmetic(self) -> tp.Tuple[tp.Any, ...]:
        """The signature without WHICH UNIT forms the products: the
        paged kernel contracts on the matrix unit (``dot``: bf16
        operands, exact in the f32 they accumulate in), the gather path
        it is held to as f32 multiply-sums (``mulsum``). What both must
        share — the accumulation dtypes of the scores and of PV, the
        mask add, the scale and its place, the softmax dtype, the
        probabilities' dtype into PV — is this tuple."""
        return (
            frozenset(acc for _, _, acc in self.qk_contracts),
            self.mask_add_dtypes, self.scale_op, self.scale_before_mask,
            self.softmax_dtype, self.probs_dtype,
            frozenset(acc for _, _, acc in self.pv_contracts),
        )

    def describe(self) -> str:
        return (
            f"qk={sorted(self.qk_contracts)} "
            f"mask_adds={sorted(self.mask_add_dtypes)} "
            f"scale={self.scale_op}"
            f"{' (before mask)' if self.scale_before_mask else ''} "
            f"softmax={self.softmax_dtype} "
            f"probs->pv={sorted(self.probs_dtype)} "
            f"pv={sorted(self.pv_contracts)}"
        )


def _canonical_contract(
    graph: FlatGraph, op: Op
) -> tp.Tuple[str, tp.Tuple[str, ...], str]:
    """Canonicalize a contraction: a ``dot_general`` keeps its operand
    dtypes with the dot's own output as the accumulation dtype; a
    ``reduce_sum``-over-``mul`` (the decode path's VPU broadcast-multiply
    form) reports the MULTIPLY's operand dtypes with the reduce's output
    as the accumulation dtype. Numerically these are the same object —
    'what dtypes are the products formed at, what dtype do they sum in'."""
    if op.prim == "dot_general" or op.prim in _CONTRACT_CALLS:
        return ("dot", op.in_dtypes, op.out_dtypes[0])
    assert op.prim == "reduce_sum", op.prim
    src = graph.producer.get(op.in_ids[0])
    if src is not None and src.prim == "mul":
        return ("mulsum", src.in_dtypes, op.out_dtypes[0])
    return ("sum", op.in_dtypes, op.out_dtypes[0])


def _backward_ops(
    graph: FlatGraph, start_ids: tp.Iterable[int], *, limit: int = 200
) -> tp.List[Op]:
    """Producer-closure walk from ``start_ids``, stopping at contraction
    boundaries (dot_general / reduce_sum) — those are collected but not
    walked past, so the slice stays inside one softmax's score path."""
    seen: tp.Set[int] = set()
    out: tp.List[Op] = []
    stack = list(start_ids)
    while stack and len(out) < limit:
        vid = stack.pop()
        op = graph.producer.get(vid)
        if op is None or op.idx in seen:
            continue
        seen.add(op.idx)
        out.append(op)
        if op.prim in ("dot_general", "reduce_sum"):
            continue  # boundary: a contraction starts a new segment
        stack.extend(i for i in op.in_ids if i >= 0)
    return out


def _leads_to_contract(
    graph: FlatGraph, vid: int, *, limit: int = 60
) -> bool:
    """Does ``vid``'s producer subtree contain a data-data contraction
    (a QK score block)? Distinguishes the score-carrying operand of a
    scale/mask op from the scalar/mask operand."""
    seen: tp.Set[int] = set()
    stack = [vid]
    while stack and len(seen) < limit:
        v = stack.pop()
        op = graph.producer.get(v)
        if op is None or op.idx in seen:
            continue
        seen.add(op.idx)
        if op.prim == "dot_general" and _dot_kind(op) == "dot":
            return True
        if op.prim == "reduce_sum":
            src = graph.producer.get(op.in_ids[0])
            if src is not None and src.prim == "mul":
                return True
            continue
        stack.extend(i for i in op.in_ids if i >= 0)
    return False


def _is_attention_exp(graph: FlatGraph, exp_op: Op) -> bool:
    """Is this ``exp`` an attention softmax? Its backward slice (stopping
    at contraction boundaries) then contains the QK score contraction —
    a data-data ``dot`` or a ``reduce_sum``-over-``mul``. A SAMPLER
    softmax (``sampling.target_probs`` over the lm-head logits in the
    temperature>0 verify program) stops at the lm-head weight projection
    instead and has neither."""
    for op in _backward_ops(graph, [i for i in exp_op.in_ids if i >= 0]):
        if op.prim == "dot_general" and _dot_kind(op) == "dot":
            return True
        if op.prim == "reduce_sum":
            src = graph.producer.get(op.in_ids[0])
            if src is not None and src.prim == "mul":
                return True
    return False


def softmax_signature(
    graph: FlatGraph, exp_op: Op
) -> SoftmaxSignature:
    """Extract the :class:`SoftmaxSignature` around one ``exp``."""
    # --- the score chain: walk BACKWARD from the softmax argument
    # through the score-carrying operand of each div/mul/add, recording
    # the order the scale and the additive mask were applied in (the
    # walk sees last-applied first)
    sub = graph.producer.get(exp_op.in_ids[0])
    chain: tp.List[str] = []  # 'div' | 'mul' | 'mask', last-applied first
    mask_adds: tp.Set[tp.Tuple[str, ...]] = set()
    vid = sub.in_ids[0] if sub is not None else exp_op.in_ids[0]
    for _ in range(32):
        op = graph.producer.get(vid)
        if op is None:
            break
        if op.prim in _PASSTHRU or op.prim == "concatenate":
            # a concatenated joint softmax: every branch shares the
            # suffix arithmetic by construction; follow branch 0
            vid = op.in_ids[0]
            continue
        if op.prim in ("div", "mul", "add"):
            score_side = [
                i for i in op.in_ids
                if i >= 0 and _leads_to_contract(graph, i)
            ]
            if not score_side:
                break
            if op.prim == "add":
                chain.append("mask")
                mask_adds.add(op.in_dtypes)
            else:
                chain.append(op.prim)
            vid = score_side[0]
            continue
        break  # the QK contraction (or something unexpected): done
    scale_op = next((c for c in chain if c != "mask"), "?")
    # the walk sees last-applied first: scale BEFORE mask means the
    # scale shows up AFTER a mask entry in the chain
    scale_before_mask = (
        "mask" in chain
        and scale_op in chain
        and chain.index(scale_op) > chain.index("mask")
    )

    # --- score contractions: the contraction boundaries of the
    # backward slice (qk-norm/rope arithmetic sits behind them and is
    # never reached; proj/rope dots are classified out)
    back = _backward_ops(graph, [i for i in exp_op.in_ids if i >= 0])
    qk: tp.Set[tp.Tuple[str, tp.Tuple[str, ...], str]] = set()
    for op in back:
        if op.prim == "dot_general" and _dot_kind(op) == "dot":
            qk.add(_canonical_contract(graph, op))
        elif op.prim == "reduce_sum":
            rec = _canonical_contract(graph, op)
            if rec[0] == "mulsum":
                qk.add(rec)
    # --- forward: exp -> reduce_sum -> div (normalize) -> [convert] -> PV
    denom_div = None
    for c in graph.consumers.get(exp_op.out_ids[0], []):
        if c.prim == "div":
            denom_div = c
            break
        if c.prim == "reduce_sum":
            for c2 in graph.consumers.get(c.out_ids[0], []):
                if c2.prim == "div":
                    denom_div = c2
                    break
    probs_dtype: tp.Set[str] = set()
    pv: tp.Set[tp.Tuple[str, tp.Tuple[str, ...], str]] = set()
    if denom_div is not None:
        frontier = [denom_div.out_ids[0]]
        hops = 0
        # the banded kernels (PR 20) slice the probability row once per
        # page band — up to MAX_BANDS slices plus their view chains —
        # so the walk needs far more than the pre-banding ~4 hops
        while frontier and hops < 256:
            hops += 1
            vid = frontier.pop()
            for c in graph.consumers.get(vid, []):
                if c.prim == "dot_general" or c.prim in _CONTRACT_CALLS:
                    pv.add(_canonical_contract(graph, c))
                    probs_dtype.add(c.in_dtypes[0])
                elif c.prim == "mul":
                    # decode's VPU form: probs * values, then reduce_sum
                    reduced = False
                    for c2 in graph.consumers.get(c.out_ids[0], []):
                        if c2.prim == "reduce_sum":
                            pv.add(_canonical_contract(graph, c2))
                            probs_dtype.add(c.in_dtypes[0])
                            reduced = True
                    if not reduced:
                        frontier.append(c.out_ids[0])
                elif c.prim in _PASSTHRU or c.prim in (
                    "concatenate", "dynamic_slice", "gather",
                ):
                    frontier.extend(c.out_ids)
    return SoftmaxSignature(
        qk_contracts=frozenset(qk),
        mask_add_dtypes=frozenset(mask_adds),
        scale_op=scale_op,
        scale_before_mask=scale_before_mask,
        softmax_dtype=exp_op.out_dtypes[0],
        probs_dtype=frozenset(probs_dtype),
        pv_contracts=frozenset(pv),
    )


def band_accumulation_order(
    graph: FlatGraph, exp_op: Op, *, kernel_body: bool = False
) -> tp.Optional[tp.Tuple[int, ...]]:
    """The PV accumulation ORDER around one attention softmax: the
    tuple of last-dim probability-row offsets of the fold's add-tree
    leaves, in the order the fold sums them.

    The banded paged kernels (PR 20, ops.paged_attn) split the PV
    contraction into per-page-band partials — each one a slice of the
    normalized probability row times its band's values — and fold them
    with ``banded_fold`` in pinned ascending-band order; the XLA
    reference runs the identical chunked reduction. f32 addition is
    not associative, so the fold's LEAF ORDER is a bitwise contract
    the dtype-level softmax signature cannot see. This extractor reads
    it straight off the jaxpr: walk forward from the normalized probs
    (the softmax's denominator ``div``) carrying the cumulative
    last-dim slice offset, mark every ``mul`` -> ``reduce_sum``
    consumer (the gather path) or matrix-unit product (the kernel: a
    ``dot_general``, or the limb helper's contract node) as one PV
    partial at its offset, then linearize the add
    tree that folds the partials — the left-to-right leaf sequence IS
    the summation order. The recent/self partial appears as the final
    leaf at offset W (its probability slice starts past the pool
    columns), so a correct fold reads strictly ascending.

    Returns None when the softmax's PV is not a probs-slice fold — the
    prefill chunk and the naive reference contract their probs with an
    einsum (``dot_general``), which has no fold and no order to pin —
    or when fewer than two partials exist. The prover's banded-order
    clause applies only to decode and verify, where None is itself a
    violation (their PV has had the mul/reduce_sum shape since PR 6)."""
    denom = None
    for c in graph.consumers.get(exp_op.out_ids[0], []):
        if c.prim == "div":
            denom = c
            break
        if c.prim == "reduce_sum":
            for c2 in graph.consumers.get(c.out_ids[0], []):
                if c2.prim == "div":
                    denom = c2
                    break
    if denom is None:
        return None
    # forward walk from the normalized probs, carrying the cumulative
    # last-dim offset; a mul -> reduce_sum consumer is one PV partial
    partials: tp.Dict[int, int] = {}
    frontier: tp.List[tp.Tuple[int, int]] = [(denom.out_ids[0], 0)]
    hops = 0
    while frontier and hops < 1024:
        hops += 1
        vid, off = frontier.pop()
        for c in graph.consumers.get(vid, []):
            if c.prim == "slice":
                noff = off + (c.meta[-1] if c.meta else 0)
                frontier.extend((o, noff) for o in c.out_ids)
            elif c.prim in _PASSTHRU:
                frontier.extend((o, off) for o in c.out_ids)
            elif c.prim == "mul" or (kernel_body and (
                    c.prim == "dot_general" or c.prim in _CONTRACT_CALLS)):
                # one PV partial: the gather path's ``mul`` ->
                # ``reduce_sum``, or — in a KERNEL BODY — a product on
                # the matrix unit (a ``dot_general``, or the limb
                # helper's node). An XLA program's einsum PV (the
                # prefill chunk, the naive reference) is no fold
                ends = [c] if c.prim != "mul" else [
                    c2 for c2 in graph.consumers.get(c.out_ids[0], [])
                    if c2.prim == "reduce_sum"
                ]
                for end in ends:
                    # the partial, and any re-view of it on the way
                    # to the fold (a keepdims reduce trails a
                    # reshape): the last view is what the adds see
                    vid = end.out_ids[0]
                    while vid is not None:
                        partials[vid] = off
                        views = [
                            v for v in graph.consumers.get(vid, [])
                            if v.prim in _PASSTHRU
                        ]
                        vid = (
                            views[0].out_ids[0]
                            if len(views) == 1 else None
                        )
    if len(set(partials.values())) < 2:
        return None
    # find the fold's root by climbing add-consumers from one partial
    # (the fold is a left spine: each add's output feeds the next)
    cur = next(reversed(partials))
    climbed = False
    for _ in range(len(partials) + 8):
        nxt = next(
            (c for c in graph.consumers.get(cur, []) if c.prim == "add"),
            None,
        )
        if nxt is None:
            break
        climbed = True
        cur = nxt.out_ids[0]
    if not climbed:
        return None

    def leaves(vid: int, depth: int = 0) -> tp.List[int]:
        op = graph.producer.get(vid)
        if op is not None and op.prim == "add" and depth < 200:
            return (
                leaves(op.in_ids[0], depth + 1)
                + leaves(op.in_ids[1], depth + 1)
            )
        return [vid]

    lv = leaves(cur)
    if any(v not in partials for v in lv):
        return None
    return tuple(partials[v] for v in lv)


# ---------------------------------------------------------------------------
# per-program choreography
# ---------------------------------------------------------------------------


def _has_kv_dequant(graph: FlatGraph) -> bool:
    """Does the graph multiply an int8-converted value — the
    ``f32(codes) * scale`` dequant of an int8 KV pool? Distinguished
    from the int8 WEIGHT path's epilogue by position: a weight's
    ``convert(s8)`` feeds its dot_general and the epilogue multiplies
    the DOT OUTPUT, while the KV dequant multiplies the converted codes
    themselves (before any contraction)."""
    for op in graph.ops:
        if op.prim != "mul":
            continue
        for vid in op.in_ids:
            v = vid
            for _ in range(8):  # chase pass-through views
                src = graph.producer.get(v)
                if src is None:
                    break
                if src.prim == "convert_element_type":
                    if src.in_dtypes[0] == "int8" and (
                        src.out_dtypes[0] in _FLOAT_DTYPES
                    ):
                        return True
                    break
                if src.prim in _PASSTHRU or src.prim in (
                    "gather", "dynamic_slice", "concatenate",
                ):
                    if not src.in_ids or src.in_ids[0] < 0:
                        break
                    v = src.in_ids[0]
                    continue
                break
    return False


def kernel_choreography(name: str, kernel_jaxpr) -> SoftmaxSignature:
    """The softmax-core signature of a Pallas kernel BODY: the body is
    ordinary jnp arithmetic over refs, so the very same extractor that
    reads the XLA programs reads it — which is the point: the kernel's
    contract (f32 score accumulation — the products' ``dot_general``
    names it as ``preferred_element_type`` —, mask before scale, one
    joint f32 softmax, f32 probs INTO the PV product: the limb helper
    is a contract node, ``_CONTRACT_CALLS``) is proven by the same
    machinery that proved the program it replaces, not by a parallel
    hand-written checklist."""
    graph = flatten_jaxpr(kernel_jaxpr)
    exps = [
        op for op in graph.ops
        if op.prim == "exp" and op.out_dtypes[0] in _FLOAT_DTYPES
    ]
    assert exps, f"{name}: kernel body contains no softmax exp"
    sig = softmax_signature(graph, exps[0])
    for e in exps[1:]:
        s2 = softmax_signature(graph, e)
        assert s2 == sig, (
            f"{name}: kernel body softmax signatures differ:\n"
            f"  {sig.describe()}\n  {s2.describe()}"
        )
    return sig


@dataclasses.dataclass(frozen=True)
class ProgramChoreography:
    """Everything the prover compares about one traced program."""

    name: str
    # the representative per-layer attention trace (all layers asserted
    # identical) and the number of layers seen
    attention: tp.Tuple[TraceRec, ...]
    n_layers: int
    softmax: SoftmaxSignature
    # the lm-head projection: operand dtypes + whether the quantized
    # dequant-epilogue multiply follows it
    lm_head: tp.Optional[TraceRec]
    lm_head_epilogue: bool
    # True when the attention runs inside a Pallas kernel (the softmax
    # signature above was extracted from the KERNEL BODY)
    kernelized: bool = False
    # the f32(s8-codes) * scale multiply of an int8 KV pool is present
    # (in the kernel body or the gathered view)
    kv_dequant: bool = False
    # the PV fold's summation order as probability-row offsets (see
    # band_accumulation_order); None for einsum-PV programs (prefill/
    # naive) where no fold exists
    band_order: tp.Optional[tp.Tuple[int, ...]] = None


def extract_choreography(name: str, closed_jaxpr) -> ProgramChoreography:
    """Normalize one traced program into its comparable choreography.

    Programs whose attention runs in the Pallas paged kernel
    (ops.paged_attn) carry the kernel call as ONE contract node in the
    attention trace; the softmax signature is then extracted from the
    KERNEL BODY (every per-layer body asserted identical), so the
    decode-choreography contract is proven about the arithmetic the
    kernel actually performs — a bf16-accumulating kernel variant turns
    the same checks red that a bf16-accumulating XLA edit would."""
    graph = flatten_jaxpr(closed_jaxpr)
    regions = attention_regions(graph)
    assert regions, f"{name}: no attention softmax found in the trace"
    rep = tuple(regions[0])
    for i, r in enumerate(regions[1:], start=2):
        assert tuple(r) == rep, (
            f"{name}: layer {i}'s attention trace differs from layer 1 "
            f"— the stacked layers do not share one choreography"
        )
    kernels = [k for k in graph.kernels if k is not None]
    kv_deq = _has_kv_dequant(graph)
    if kernels:
        sigs = {kernel_choreography(name, k) for k in kernels}
        assert len(sigs) == 1, (
            f"{name}: per-layer kernel bodies disagree:\n" + "\n".join(
                s.describe() for s in sigs
            )
        )
        sig = next(iter(sigs))
        kv_deq = kv_deq or any(
            _has_kv_dequant(flatten_jaxpr(k)) for k in kernels
        )
        # the banded-accumulation order is a property of the KERNEL
        # BODY's PV fold (the outer trace sees only the contract node)
        kgraph = flatten_jaxpr(kernels[0])
        kexps = [
            op for op in kgraph.ops
            if op.prim == "exp" and op.out_dtypes[0] in _FLOAT_DTYPES
        ]
        band_order = (
            band_accumulation_order(kgraph, kexps[0], kernel_body=True)
            if kexps else None
        )
    else:
        exps = [
            op for op in graph.ops
            if op.prim == "exp" and op.out_dtypes[0] in _FLOAT_DTYPES
            and _is_attention_exp(graph, op)
        ]
        sig = softmax_signature(graph, exps[0])
        for e in exps[1:]:
            s2 = softmax_signature(graph, e)
            assert s2 == sig, (
                f"{name}: softmax signatures differ between layers:\n"
                f"  {sig.describe()}\n  {s2.describe()}"
            )
        band_order = band_accumulation_order(graph, exps[0])
    # lm head: the LAST weight projection in program order, plus its
    # epilogue (a following multiply whose other operand is an entry
    # parameter — the QuantLinear per-channel scale)
    lm = None
    lm_op = None
    for op in graph.ops:
        if op.prim == "dot_general" and _dot_kind(op) == "proj":
            lm_op = op
    if lm_op is not None:
        lm = ("proj", lm_op.in_dtypes, lm_op.out_dtypes)
    epilogue = False
    if lm_op is not None:
        for c in graph.consumers.get(lm_op.out_ids[0], []):
            if c.prim == "mul" and "invar" in c.in_origins:
                epilogue = True
    return ProgramChoreography(
        name=name,
        attention=rep,
        n_layers=len(regions),
        softmax=sig,
        lm_head=lm,
        lm_head_epilogue=epilogue,
        kernelized=bool(kernels),
        kv_dequant=kv_deq,
        band_order=band_order,
    )


# ---------------------------------------------------------------------------
# the prover
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChoreoCheck:
    name: str
    ok: bool
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class ChoreoReport:
    checks: tp.Tuple[ChoreoCheck, ...]
    programs: tp.Tuple[ProgramChoreography, ...]

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.checks)

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "ok": self.ok,
            "checks": [
                {"name": c.name, "ok": c.ok, "detail": c.detail}
                for c in self.checks
            ],
            "programs": {
                p.name: {
                    "n_layers": p.n_layers,
                    "attention_ops": len(p.attention),
                    "attention": [
                        [k, list(i), list(o)] for k, i, o in p.attention
                    ],
                    "softmax": p.softmax.describe(),
                    "lm_head": list(p.lm_head) if p.lm_head else None,
                    "lm_head_epilogue": p.lm_head_epilogue,
                    "kernelized": p.kernelized,
                    "kv_dequant": p.kv_dequant,
                    "band_order": (
                        list(p.band_order)
                        if p.band_order is not None
                        else None
                    ),
                }
                for p in self.programs
            },
        }


def _first_diff(a: tp.Sequence[TraceRec], b: tp.Sequence[TraceRec]) -> str:
    for i, (ra, rb) in enumerate(zip(a, b)):
        if ra != rb:
            return f"op {i}: {ra} != {rb}"
    if len(a) != len(b):
        return f"length {len(a)} != {len(b)}"
    return ""


def prove_choreography(
    decode: ProgramChoreography,
    prefill: ProgramChoreography,
    verify: ProgramChoreography,
    naive: ProgramChoreography,
    *,
    expect_kv_dequant: bool = False,
) -> ChoreoReport:
    """Evaluate the three serving-choreography contracts (module
    docstring). ``naive`` is the reference trace of
    ``ops.attention.naive_attention`` — what the monolithic prefill (and
    the training forward) computes. ``expect_kv_dequant`` (the int8 KV
    pool): all three programs must carry the ``f32(codes) * scale``
    dequant multiply of the quantized pool — a program reading raw codes
    without its scale would be arithmetically wrong in a way no dtype
    check sees, so presence of the scale-multiply is itself a proven
    contract (and conversely, a float pool must NOT carry one)."""
    checks: tp.List[ChoreoCheck] = []

    # 1. verify mirrors decode OP FOR OP (the PR 5 contract)
    diff = _first_diff(decode.attention, verify.attention)
    sig_ok = decode.softmax == verify.softmax
    checks.append(ChoreoCheck(
        name="verify-mirrors-decode",
        ok=not diff and sig_ok,
        detail=diff or (
            ""
            if sig_ok
            else f"softmax: {decode.softmax.describe()} != "
            f"{verify.softmax.describe()}"
        ),
    ))

    # 2. the prefill chunk's softmax core mirrors naive_attention (the
    # PR 4 contract); full-sequence equality is not expected (the chunk
    # has a pool score block and rope/qk-norm the bare reference lacks)
    checks.append(ChoreoCheck(
        name="prefill-mirrors-naive",
        ok=prefill.softmax == naive.softmax,
        detail=(
            ""
            if prefill.softmax == naive.softmax
            else f"{prefill.softmax.describe()} != "
            f"{naive.softmax.describe()}"
        ),
    ))

    # 3. shared arithmetic across all three serving programs
    progs = (decode, prefill, verify)
    shared: tp.List[tp.Tuple[str, bool, str]] = []
    sm = {p.softmax.softmax_dtype for p in progs}
    shared.append((
        "softmax runs in f32 everywhere",
        sm == {"float32"},
        f"softmax dtypes {sorted(sm)}",
    ))
    # extraction-degeneracy guard: a signature with NO score
    # contractions or an unrecognized scale op means the program's
    # softmax no longer has the shape the extractor (and the contract)
    # expects — that is a violation, not a vacuous pass. Found by fault
    # injection: a bf16-accumulating kernel variant used to slip through
    # because jnp's silent re-promotion broke the score-chain walk and
    # left every dtype set empty.
    degenerate = {
        p.name: (not p.softmax.qk_contracts, p.softmax.scale_op)
        for p in progs
    }
    shared.append((
        "every program exposes its score contractions to the prover",
        all(
            p.softmax.qk_contracts and p.softmax.scale_op in ("div", "mul")
            and p.softmax.pv_contracts
            for p in progs
        ),
        f"degenerate signatures: {degenerate}",
    ))
    # PV accumulation is contract-specific (decode keeps f32 probs and
    # sums, the prefill chunk mirrors naive_attention's value-dtype
    # einsum) and is pinned per program by checks 1 and 2 — the SHARED
    # invariant is the score accumulation
    accs = {
        acc for p in progs for (_, _, acc) in p.softmax.qk_contracts
    }
    shared.append((
        "scores accumulate in f32 everywhere",
        accs == {"float32"},
        f"score accumulation dtypes {sorted(accs)}",
    ))
    sbm = {p.softmax.scale_before_mask for p in progs}
    shared.append((
        "mask is added before the softmax scale everywhere",
        sbm == {False},
        f"scale_before_mask {sorted(sbm)}",
    ))
    heads = {(p.lm_head, p.lm_head_epilogue) for p in progs}
    shared.append((
        "lm-head projection choreography is identical everywhere",
        len(heads) == 1,
        "; ".join(
            f"{p.name}: {p.lm_head} epilogue={p.lm_head_epilogue}"
            for p in progs
        ),
    ))
    layer_depths = {p.n_layers for p in progs}
    shared.append((
        "all programs traced at one depth",
        len(layer_depths) == 1,
        f"layer counts {sorted(layer_depths)}",
    ))
    deq = {p.name: p.kv_dequant for p in progs}
    if expect_kv_dequant:
        shared.append((
            "int8 KV: every program dequantizes the pool "
            "(codes * per-page scale)",
            all(deq.values()),
            f"kv_dequant {deq}",
        ))
    else:
        shared.append((
            "float KV: no stray int8 pool dequant anywhere",
            not any(deq.values()),
            f"kv_dequant {deq}",
        ))
    # banded PV accumulation order (PR 20): the decode and verify PV
    # folds — kernel body and banded XLA reference alike — must sum
    # their page-band partials in pinned ASCENDING-band order, with the
    # recent/self partial last (its probability slice starts past the
    # pool columns, so a correct fold reads strictly ascending), and
    # the two programs must agree exactly. f32 addition is not
    # associative: a reordered fold is a bitwise drift no dtype check
    # sees (the fault injection in tests/test_choreo.py reverses
    # ops.paged_attn._BAND_FOLD_ORDER and must fail exactly this
    # clause). The prefill chunk and naive reference contract their
    # probs with an einsum — no fold exists, band_accumulation_order
    # returns None for them, and they are exempt by construction; for
    # decode/verify a None is itself a violation (their PV lost the
    # shape the extractor pins).
    def _ascending(t: tp.Optional[tp.Tuple[int, ...]]) -> bool:
        return t is not None and all(a < b for a, b in zip(t, t[1:]))

    shared.append((
        "banded PV accumulation runs in pinned ascending-band order",
        _ascending(decode.band_order) and _ascending(verify.band_order)
        and decode.band_order == verify.band_order,
        f"band_order decode={decode.band_order} "
        f"verify={verify.band_order}",
    ))
    # decode and verify must agree on WHERE the attention runs (both in
    # the kernel or both in XLA) — a half-kernelized pair could pass the
    # per-program checks while running two different arithmetic stacks
    shared.append((
        "decode and verify share one attention backend",
        decode.kernelized == verify.kernelized,
        f"kernelized decode={decode.kernelized} "
        f"verify={verify.kernelized}",
    ))
    for name, ok, detail in shared:
        checks.append(ChoreoCheck(
            name=f"shared: {name}", ok=ok, detail="" if ok else detail
        ))

    return ChoreoReport(
        checks=tuple(checks),
        programs=(decode, prefill, verify, naive),
    )


def prove_sp_choreography(
    off: ProgramChoreography,
    sp: ProgramChoreography,
) -> ChoreoReport:
    """The sequence-parallel prefill contract: the SP chunk program
    (``ServingEngine(prefill_sp="on")``) must be the plain chunk program
    PLUS DATA MOVEMENT AND NOTHING ELSE. Row-sharding the chunk's
    replicated segments over the 'tensor' axis inserts only
    ``sharding_constraint`` ops — pass-through, outside the arithmetic
    alphabet — so the two programs' normalized traces must be IDENTICAL
    op for op: one differing record means SP changed arithmetic, which
    is exactly the bitwise-identity hazard (a reduce-scatter substituted
    for an all-reduce reassociates the psum and flips near-tied greedy
    argmaxes the same way the PR 4/PR 5 drifts did). Both traces must
    come from the same mesh so the comparison isolates the prefill_sp
    knob."""
    checks: tp.List[ChoreoCheck] = []
    diff = _first_diff(off.attention, sp.attention)
    checks.append(ChoreoCheck(
        name="sp-prefill-mirrors-off",
        ok=not diff,
        detail=diff,
    ))
    sig_ok = off.softmax == sp.softmax
    checks.append(ChoreoCheck(
        name="sp-prefill-softmax-identical",
        ok=sig_ok,
        detail=(
            ""
            if sig_ok
            else f"{off.softmax.describe()} != {sp.softmax.describe()}"
        ),
    ))
    head_ok = (
        off.lm_head == sp.lm_head
        and off.lm_head_epilogue == sp.lm_head_epilogue
    )
    checks.append(ChoreoCheck(
        name="sp-prefill-lm-head-identical",
        ok=head_ok,
        detail=(
            ""
            if head_ok
            else f"{off.lm_head} ep={off.lm_head_epilogue} != "
            f"{sp.lm_head} ep={sp.lm_head_epilogue}"
        ),
    ))
    struct_ok = (
        off.n_layers == sp.n_layers
        and off.kernelized == sp.kernelized
        and off.kv_dequant == sp.kv_dequant
    )
    checks.append(ChoreoCheck(
        name="sp-prefill-structure-identical",
        ok=struct_ok,
        detail=(
            ""
            if struct_ok
            else f"layers {off.n_layers}/{sp.n_layers} kernelized "
            f"{off.kernelized}/{sp.kernelized} kv_dequant "
            f"{off.kv_dequant}/{sp.kv_dequant}"
        ),
    ))
    return ChoreoReport(checks=tuple(checks), programs=(off, sp))


# ---------------------------------------------------------------------------
# the sampled-verify prover (temperature > 0)
# ---------------------------------------------------------------------------

# comparison primitives — deliberately OUTSIDE _ARITH (a compare is a
# decision, not arithmetic, so normalized traces drop it), collected
# explicitly here because the sampled acceptance test IS a float compare
# whose dtype decides near-tie accept/reject flips
_COMPARES = frozenset({"lt", "le", "gt", "ge"})


def _rng_downstream_ids(graph: FlatGraph) -> tp.Set[int]:
    """Value ids computed downstream of any PRNG draw (``random_bits``
    outputs, forward consumer closure). In a sampled program this is
    everything the drawn randomness can influence — the gumbel
    arithmetic, the categorical argmax, and (in the verify program) the
    acceptance compare and anything fed by an accepted token."""
    seen: tp.Set[int] = set()
    stack = [
        oid for op in graph.ops if op.prim == "random_bits"
        for oid in op.out_ids
    ]
    seen.update(stack)
    while stack:
        vid = stack.pop()
        for op in graph.consumers.get(vid, []):
            for oid in op.out_ids:
                if oid not in seen:
                    seen.add(oid)
                    stack.append(oid)
    return seen


def _slice_records(ops: tp.Iterable[Op]) -> tp.Tuple[TraceRec, ...]:
    """Sorted float-arithmetic records of an op slice — a multiset
    fingerprint (program order varies legitimately between the decode
    window's in-scan sampler and the verify program's row-0 sampler;
    what must agree is which float ops run at which dtypes)."""
    return tuple(sorted(
        (
            _dot_kind(op) if op.prim == "dot_general" else op.prim,
            op.in_dtypes,
            op.out_dtypes,
        )
        for op in ops
        if op.prim in _ARITH and _is_float_op(op)
    ))


@dataclasses.dataclass(frozen=True)
class SamplerChoreography:
    """The sampled-path dtype choreography of one traced program: what
    the temperature>0 prover compares between the decode window's
    sampler and the verify program's rejection-sampling acceptance."""

    name: str
    # sorted float-arith records of the backward slice of each
    # categorical argmax (jax lowers ``random.categorical`` to
    # argmax(logits/T + gumbel), so this slice IS the sampler: the
    # temperature division, the top-k mask arithmetic, the gumbel
    # -log(-log u) chain) — all categoricals asserted identical
    categorical: tp.Tuple[TraceRec, ...]
    n_categoricals: int
    # (prim, operand dtypes) of every float comparison downstream of the
    # PRNG — in the verify program the rejection-sampling acceptance
    # test ``u * q(t) <= p(t)`` lives here (the decode window has none:
    # its sampler decides by argmax, not threshold)
    rng_float_compares: tp.Tuple[tp.Tuple[str, tp.Tuple[str, ...]], ...]
    # {sub, max, div, log} records of the residual-resample slice — the
    # backward slice of the residual ``log`` (the one float log NOT in
    # any categorical's gumbel chain): ``max(p - q, 0)`` and its
    # renormalization (verify only; empty for the decode window)
    residual: tp.Tuple[TraceRec, ...]
    # the target-softmax ``exp`` inside the residual slice (the
    # ``target_probs`` softmax the acceptance threshold and residual are
    # computed from), None when absent
    residual_exp: tp.Optional[TraceRec]


def extract_sampler_choreography(
    name: str, closed_jaxpr
) -> SamplerChoreography:
    """Normalize one SAMPLED (temperature > 0) traced program into its
    comparable sampler choreography. Purely structural — no execution;
    degenerate extractions (no categorical, no residual log) are
    reported as empty fields and turned into failing checks by
    :func:`prove_sampled_choreography`, never silently passed."""
    graph = flatten_jaxpr(closed_jaxpr)
    rng_ids = _rng_downstream_ids(graph)
    argmaxes = [
        op for op in graph.ops
        if op.prim == "argmax"
        and op.in_dtypes and op.in_dtypes[0] in _FLOAT_DTYPES
        # the CATEGORICAL argmax consumes logits + gumbel noise; a
        # greedy/verification argmax reads deterministic logits only
        and any(i in rng_ids for i in op.in_ids if i >= 0)
    ]
    cat_op_idxs: tp.Set[int] = set()
    cat_sigs: tp.List[tp.Tuple[TraceRec, ...]] = []
    for am in argmaxes:
        ops = _backward_ops(
            graph, [i for i in am.in_ids if i >= 0]
        )
        cat_op_idxs.update(op.idx for op in ops)
        cat_sigs.append(_slice_records(ops))
    categorical: tp.Tuple[TraceRec, ...] = ()
    if cat_sigs:
        categorical = cat_sigs[0]
        assert all(s == categorical for s in cat_sigs[1:]), (
            f"{name}: categorical sampler slices disagree within one "
            f"program"
        )
    compares = tuple(
        (op.prim, op.in_dtypes)
        for op in graph.ops
        if op.prim in _COMPARES
        and op.in_dtypes and op.in_dtypes[0] in _FLOAT_DTYPES
        and any(i in rng_ids for i in op.in_ids if i >= 0)
    )
    # the residual-resample slice: every float log that is NOT gumbel
    # arithmetic (gumbel logs live in a categorical's backward slice)
    # roots the residual renormalization log(normalize(max(p - q, 0)))
    resid_logs = [
        op for op in graph.ops
        if op.prim == "log" and op.out_dtypes[0] in _FLOAT_DTYPES
        and op.idx not in cat_op_idxs
    ]
    resid_ops: tp.Dict[int, Op] = {}
    for lg in resid_logs:
        for op in _backward_ops(
            graph, [i for i in lg.in_ids if i >= 0]
        ):
            resid_ops[op.idx] = op
        resid_ops[lg.idx] = lg
    residual = tuple(
        rec for rec in _slice_records(resid_ops.values())
        if rec[0] in ("sub", "max", "div", "log")
    )
    exps = [
        op for op in resid_ops.values()
        if op.prim == "exp" and op.out_dtypes[0] in _FLOAT_DTYPES
    ]
    residual_exp = (
        ("exp", exps[0].in_dtypes, exps[0].out_dtypes) if exps else None
    )
    return SamplerChoreography(
        name=name,
        categorical=categorical,
        n_categoricals=len(argmaxes),
        rng_float_compares=compares,
        residual=residual,
        residual_exp=residual_exp,
    )


def prove_sampled_choreography(
    decode: SamplerChoreography,
    verify: SamplerChoreography,
) -> tp.Tuple[ChoreoCheck, ...]:
    """The four sampled-verify contracts, as checks to append to a
    temperature>0 :class:`ChoreoReport`:

    1. the verify program's row-0 categorical is the decode window's
       sampler op for op (same tempered/top-k/gumbel dtype records) —
       the sampled analogue of verify-mirrors-decode;
    2. every float comparison the drawn randomness feeds — the
       rejection-sampling acceptance test among them — runs in f32 (a
       bf16 acceptance compare flips near-tie accept/reject decisions
       exactly the way the PR 5 bf16 argmax flipped near-tie
       acceptance);
    3. the residual renormalization ``max(p - q, 0) / mass`` and its
       log-encoding run in f32;
    4. the target softmax feeding the acceptance threshold and the
       residual runs in f32.

    Degeneracy is failure: a sampled program in which the extractor
    finds no categorical, no acceptance compare, or no residual slice
    no longer has the shape the contract is about."""
    checks: tp.List[ChoreoCheck] = []

    ok1 = (
        decode.n_categoricals >= 1
        and verify.n_categoricals >= 1
        and decode.categorical == verify.categorical
    )
    checks.append(ChoreoCheck(
        name="sampled: verify row-0 sampler mirrors the decode window's "
        "categorical",
        ok=ok1,
        detail="" if ok1 else (
            f"decode categoricals={decode.n_categoricals} "
            f"{decode.categorical} != verify "
            f"categoricals={verify.n_categoricals} {verify.categorical}"
        ),
    ))

    bad = [
        (p, d) for (p, d) in verify.rng_float_compares
        if set(d) != {"float32"}
    ]
    ok2 = bool(verify.rng_float_compares) and not bad
    checks.append(ChoreoCheck(
        name="sampled: acceptance compares run in f32",
        ok=ok2,
        detail="" if ok2 else (
            f"non-f32 float compares downstream of the PRNG: {bad}"
            if bad else "no float compare downstream of the PRNG — the "
            "acceptance test is missing from the verify program"
        ),
    ))

    bad_r = [r for r in verify.residual if set(r[1]) | set(r[2]) != {"float32"}]
    ok3 = bool(verify.residual) and not bad_r
    checks.append(ChoreoCheck(
        name="sampled: residual renormalization runs in f32",
        ok=ok3,
        detail="" if ok3 else (
            f"non-f32 residual records: {bad_r}" if bad_r
            else "no residual-resample slice found in the verify program"
        ),
    ))

    ok4 = (
        verify.residual_exp is not None
        and set(verify.residual_exp[1]) | set(verify.residual_exp[2])
        == {"float32"}
    )
    checks.append(ChoreoCheck(
        name="sampled: target softmax runs in f32 in the verify sampler",
        ok=ok4,
        detail="" if ok4 else (
            f"target softmax exp: {verify.residual_exp}"
        ),
    ))
    return tuple(checks)

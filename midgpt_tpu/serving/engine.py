"""Continuous-batching serving engine: paged KV pool + fused K-step decode.

The fixed-batch sampler (midgpt_tpu.sampling.generate) holds one ring
cache sized per request batch and dispatches every decode step; under real
traffic that leaves decode slots idle whenever requests finish early and
pays the full per-dispatch latency once per generated token. This engine
replaces both:

- **Paged KV** (serving.paged): requests own page lists in a shared pool,
  so admission is a page allocation, eviction a free — no cache reshapes.
- **Prefix caching with copy-on-write sharing** (serving.paged): pages
  are refcounted and full pages are content-indexed by their token
  prefix chain; a new request's block table points straight at already-
  resident pages of any live or finished request, its prefill computes
  only the uncached suffix, and the one partially-shared page is copied
  before the request may append (never two writers on a page). Finished
  requests' pages go COLD (refcount 0, still resident) and serve future
  hits until page pressure reclaims them, LRU, leaves first.
- **Chunked prefill** (Sarathi-style): long prompts prefill in fixed-
  token-budget chunks interleaved between the fused decode windows
  instead of monopolizing one, bounding TTFT for co-scheduled requests;
  a chunk resumes mid-prompt from the partially-built block table
  (models.gpt.prefill_chunk_paged), so chunking is exact, not windowed.
  The budget is handed out in granules of ``prefill_chunk`` tokens, and
  a slot's granules of one window run as one program, so the weights
  are read once for them.
- **Continuous batching**: a host-side scheduler admits queued requests
  into free decode slots at every window boundary, interleaves their
  prefills with decode, and evicts (re-queues with progress kept) under
  page pressure — slots stay full under mixed traffic.
- **Self-speculative decoding** (serving.speculate + the verify program
  below): a host-side n-gram proposer drafts up to ``speculate`` tokens
  per request from the request's OWN prompt+generated history (prompt-
  lookup style — no draft model, composes with every config), and one
  jitted pool/logits-donating dispatch scores all slots' ``spec_len+1``
  candidate rows in one joint-softmax multi-query pass whose arithmetic
  mirrors the decode window's op for op (gpt.verify_paged_at — bf16
  near-ties flip under any other dtype choreography).
  Acceptance is longest-prefix: argmax agreement at ``temperature ==
  0``, REJECTION SAMPLING at ``temperature > 0`` (accept draft t with
  probability ``min(1, p_target(t)/q_draft(t))`` against the decode
  sampler's own tempered/top-k distribution; on rejection the carried
  logits encode the normalized residual ``max(p - q, 0)`` so the next
  dispatch's row-0 draw IS the resample — see _build_verify_program).
  Each dispatch emits 1 + accepted tokens (the "+1" is the previous
  dispatch's bonus token, materialized from the carried logits).
  Rejected rows roll back via a per-slot write watermark: their K/V
  never lands in the pages, so the single-writer / refcount /
  prefix-index invariants are untouched. Greedy outputs are
  token-identical to the non-speculative engine, sampled outputs are
  distributed exactly as it and keep its bitwise scheduling invariance
  — speculation changes the dispatch count, not the stream contract.
- **Int8 quantized weight path** (``quant="int8"``, midgpt_tpu.quant):
  every program the engine compiles streams int8 per-output-channel
  weights with the dequantization fused into each matmul's epilogue —
  halving the per-token weight HBM stream that dominates the decode
  floor. Po2 scales keep greedy output token-identical to the engine
  running the dequantized weights; the programs take the model as an
  ENTRY PARAMETER (closed over, jax would bake the weights in as
  constants — and constant-fold the quantized dequant back to f32).
- **Fused multi-token dispatch** (the PR 2 design, ported to decode): one
  jitted, state-donating ``lax.scan`` runs K whole-model decode steps —
  all layers, sampling, and the bulk page flush — per XLA launch.
  Per-slot EOS/length masks are carried IN-SCAN: finished requests pad
  harmlessly (writes dropped, emissions masked) until the next host-side
  swap boundary. Dispatches per generated token drop from 1 per token to
  1/K per active batch.

Determinism contract: per-request sampling keys derive from
``fold_in(fold_in(key, request_seed), tokens_emitted_so_far)``
(sampling.derive_request_key) — the token stream of a request is a
function of the request alone, independent of which slot it lands in,
the window size K, batch composition, any mid-run eviction/re-admission,
prefix-cache hits, and prefill chunking. Speculation at temperature > 0
keeps the contract: its acceptance uniforms come from a SALTED substream
of the same per-position derived key (sampling.SPEC_ACCEPT_SALT), so
they too are functions of (request seed, stream position) only.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from midgpt_tpu.models.gpt import (
    GPT,
    decode_step_paged,
    prefill_chunk_paged,
    verify_tokens_paged,
)
from midgpt_tpu.serving.faults import (
    AdmissionRejected,
    HandoffFailed,
    PoolOverloaded,
)
from midgpt_tpu.serving.speculate import NgramProposer, Proposer
from midgpt_tpu.serving.telemetry import (
    EngineTelemetry,
    MetricsRegistry,
    write_json,
)
from midgpt_tpu.telemetry import span
from midgpt_tpu.serving.paged import (
    HostSpillStore,
    PageAllocator,
    PagedKVPool,
    PrefixIndex,
    RecurrentState,
    copy_page,
    export_pages,
    flush_recent,
    import_pages,
    pages_needed,
    write_token_rows,
)

Array = jax.Array

# events the log of a profiler session keeps (ServingEngine._session_edge):
# a step writes one a decoding slot and a few more, so minutes of steps
SESSION_RING = 65536

# rows at which a prefill-chunk program stops being bound by its weight
# stream: a bf16 matmul over M rows does 2·M·K·N FLOP for 2·K·N bytes of
# weights, M FLOP a byte, and a v5e chip does 197 TFLOP/s over 819 GB/s =
# ~240 FLOP a byte, rounded to the page buckets' 256. Below it a longer
# program shares one read of the weights among more rows; above it the
# matrix unit bounds the program and a longer one only pads more. A slot's
# prefill granules of one engine step run as one program of at most
# max(prefill_chunk, PREFILL_MERGE_ROWS) rows (ServingEngine._run_prefills)
PREFILL_MERGE_ROWS = 256


# ---------------------------------------------------------------------------
# Compiled programs
# ---------------------------------------------------------------------------

# Program cache: since the model is an ENTRY PARAMETER (not a closure
# constant — see window_fn), a program factory's output depends only on
# the model CONFIG and the scalar geometry, so identical geometries
# share one jitted callable — and therefore one XLA compilation per
# model structure/dtype (jax.jit caches per wrapper; a fresh wrapper
# per ServingEngine would recompile the same program every time an
# engine is constructed, which the test suite does dozens of times).
_PROGRAM_CACHE: tp.Dict[tp.Tuple, tp.Any] = {}


def serving_logical_rules(prefill_sp: str = "off") -> tp.Dict[str, tp.Any]:
    """The activation logical-rule table the serving programs compile
    under: the training table with 'batch' and 'seq' unmapped. Inside
    ONE engine the slot dim is NEVER a sharded axis — data parallelism
    is shared-nothing engine replicas (serving.cluster), and a
    replica/fsdp axis on the engine's own mesh must ride replicated.
    (Left on the training mapping, the model's generic
    ``shard_act(x, 'batch', ...)`` tags would shard slots over
    'replica', and the partitioner then bounces every per-slot
    activation between sharded and replicated through the page
    gathers — the exact batch all-gather the
    no-batch-allgather-in-page-gather audit rule flags; found by that
    rule on the first tp=2,replica=2 audit.) 'seq' is unmapped for the
    same reason: decode is one token deep and a prefill chunk is one
    slot wide — there is nothing to shard.

    The one exception is the SP prefill-chunk program
    (``prefill_sp="on"``): a long-prompt chunk IS many tokens deep, and
    its replicated per-token segments shard their rows over 'tensor'
    through the dedicated 'sp' logical axis
    (models.gpt.prefill_chunk_paged sp=True). 'sp' stays unmapped for
    every other program — decode/verify never see the axis, so no
    decode bytes move when the knob flips."""
    from midgpt_tpu.parallel.sharding import DEFAULT_LOGICAL_RULES

    assert prefill_sp in ("on", "off"), prefill_sp
    rules = {**DEFAULT_LOGICAL_RULES, "batch": None, "seq": None}
    if prefill_sp == "on":
        rules["sp"] = "tensor"
    return rules


def _mesh_key(mesh) -> tp.Optional[tp.Tuple]:
    """Explicit cache fingerprint of a serving mesh: axis names/sizes AND
    the concrete device ids. Program identity depends on both — a tp=2
    engine must never reuse a tp=1 program (different partitioning), and
    two DP replicas pinned to disjoint device sets must not share a
    wrapper either (same geometry, different placement — jax.jit would
    recompile per sharding anyway, but sharing the wrapper would
    interleave two replicas' executable caches and hide placement bugs
    from the cache-distinctness test). ``None`` stays ``None`` (the
    single-chip path)."""
    if mesh is None:
        return None
    return (
        tuple(mesh.shape.items()),
        tuple(d.id for d in mesh.devices.flat),
    )


def _cached_program(key: tp.Tuple, build: tp.Callable[[], tp.Any]):
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        fn = build()
        _PROGRAM_CACHE[key] = fn
    return fn


def make_decode_window(
    model: GPT,
    *,
    slots: int,
    window: int,
    pmax: int,
    rope_len: int,
    pad_id: int = 0,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
    mesh=None,
    paged_kernel: str = "xla",
):
    # paged_kernel sits BEFORE the mesh fingerprint: the
    # fingerprint stays the key's last element (the cache-distinctness
    # test and any cache introspection key off that position)
    key = (
        "decode_window", model.config, slots, window, pmax, rope_len,
        pad_id, temperature, top_k, paged_kernel, _mesh_key(mesh),
    )
    return _cached_program(
        key,
        lambda: _build_decode_window(
            model.config, slots=slots, window=window, pmax=pmax,
            rope_len=rope_len, pad_id=pad_id, temperature=temperature,
            top_k=top_k, mesh=mesh, paged_kernel=paged_kernel,
        ),
    )


def _build_decode_window(
    cfg,
    *,
    slots: int,
    window: int,
    pmax: int,
    rope_len: int,
    pad_id: int,
    temperature: float,
    top_k: tp.Optional[int],
    mesh,
    paged_kernel: str = "xla",
):
    """The fused K-step decode program: ONE jitted, pool/logits-donating
    ``lax.scan`` over ``window`` whole-model decode steps.

    Per scan step: sample each slot's next token from the carried logits,
    mark slots that just hit EOS/length done, run the paged decode step
    (models.gpt.decode_step_paged) for all slots SIMD-style, and collect
    (token, emit-mask, write-mask) as scan outputs. After the scan the
    window's recent K/V rows flush into the pages in one bulk scatter —
    still inside the same compiled program, so steady-state decode is
    exactly one XLA dispatch per K generated tokens per active batch.

    Finished/empty slots ride along masked: they sample pad, their page
    writes route to the drop sentinel, and their emissions are masked out
    host-side — the scan shape never depends on traffic. Slots still
    mid-prefill ride the same way (``done`` carries them), so chunked
    prefill and decode interleave without a second program shape.
    """
    from midgpt_tpu.parallel.sharding import axis_rules, shard_act
    from midgpt_tpu.sampling import derive_request_key, sample_token

    rshape = (cfg.kv_layers, slots, cfg.pool_heads, window, cfg.pool_width)
    hybrid = cfg.linear_layers > 0
    # a latent-attention model: one recent buffer (the pooled rows), a slot
    # whose token is none claims no expert, and of its expert layers the
    # window counts the rows routed, as the block window does
    latent, counting = cfg.latent, cfg.latent and cfg.expert_layers > 0

    def window_fn(
        model: GPT,  # ENTRY PARAMETER, not a closure constant: closed
        # over, jax bakes every weight into the executable as an HLO
        # constant — and for a quantized model XLA then CONSTANT-FOLDS
        # the dequant (convert + scale) into full f32 weight matrices,
        # silently doubling the weight stream the int8 path exists to
        # halve (caught by the no-dequant-materialization audit)
        pool: PagedKVPool,  # DONATED
        logits: Array,  # [S, V] f32 — per-slot next-token logits; DONATED
        bt: Array,  # [S, Pmax] int32 block tables
        pooled_len: Array,  # [S] int32 — tokens resident in the pool
        done: Array,  # [S] bool — finished or empty slot
        emitted: Array,  # [S] int32 — tokens emitted so far per request
        budget: Array,  # [S] int32 — max_new_tokens per request
        eos: Array,  # [S] int32 — per-request EOS id (-1 = none)
        seeds: Array,  # [S] int32 — per-request sampling seed
        key: Array,  # base PRNG key (engine-constant)
        state: tp.Optional[RecurrentState] = None,  # DONATED: a model
        # with linear-attention layers carries it through the K steps as
        # it carries the recent buffers, and returns it last
    ):
        assert bt.shape == (slots, pmax), (
            f"block table {bt.shape} != declared geometry ({slots}, {pmax})"
        )
        assert (state is not None) == hybrid
        with axis_rules(mesh, serving_logical_rules()):
            # recent rows travel in the pool's ROW dtype: the pool dtype
            # for float pools, bf16 grid-rounded values for int8 pools
            # (PagedKVPool.row_dtype)
            rk = jnp.zeros(rshape, pool.row_dtype)
            rv = None if latent else jnp.zeros(rshape, pool.row_dtype)

            def sample(lg, em):
                if temperature == 0.0:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
                # per-request key stream: (seed, emitted-count) — slot-,
                # window-, and eviction-invariant
                ks = jax.vmap(
                    lambda sd, ti: derive_request_key(key, sd, ti)
                )(seeds, em)
                return jax.vmap(
                    lambda l1, k1: sample_token(
                        l1[None], k1, temperature, top_k
                    )[0]
                )(lg, ks)

            def body(carry, r):
                logits, rk, rv, done, emitted, *st = carry
                pre_done = done
                tok = sample(logits, emitted)
                tok = jnp.where(pre_done, jnp.int32(pad_id), tok)
                emitted = emitted + (~pre_done).astype(jnp.int32)
                hit_eos = (~pre_done) & (tok == eos)
                hit_len = (~pre_done) & (emitted >= budget)
                done = pre_done | hit_eos | hit_len
                # the just-sampled token is this step's model input; its
                # K/V row is only needed if a real token can follow it
                write_valid = ~done
                pos = pooled_len + r  # per-slot absolute position
                # (linear-attention layers: a slot's state and tail move
                # only where a real token can follow this one, so finished,
                # empty and still-prefilling slots ride through untouched)
                new_logits, rk, rv, *st = decode_step_paged(
                    model, tok, pos, pool.k, pool.v, bt, rk, rv, r,
                    pooled_len, rope_len, pool_sk=pool.scale_k,
                    pool_sv=pool.scale_v, paged_kernel=paged_kernel,
                    **({"state": st[0], "valid": write_valid} if st else {}),
                    **({"valid": write_valid} if latent else {}),
                )
                rows = (st.pop(),) if counting else ()  # [Le, E]
                # the carry is f32 regardless of compute dtype (an exact
                # widening — sampling sees the same values either way)
                new_logits = new_logits.astype(logits.dtype)
                return (
                    (new_logits, rk, rv, done, emitted, *st),
                    (tok, ~pre_done, write_valid, *rows),
                )

            st = ((state.s, state.conv),) if hybrid else ()
            (logits, rk, rv, done, emitted, *st), (
                toks, emit, wvalid, *rows
            ) = jax.lax.scan(
                body,
                (logits, rk, rv, done, emitted, *st),
                jnp.arange(window, dtype=jnp.int32),
            )
            pool = flush_recent(
                pool, rk, rv, bt, pooled_len, jnp.transpose(wvalid)
            )
            new_len = pooled_len + jnp.sum(wvalid.astype(jnp.int32), axis=0)
            # pin the donated logits carry vocab-sharded on the way out
            # (same spec the engine committed the input with — donation
            # silently drops if the output resharded)
            logits = shard_act(logits, None, "vocab")
        if hybrid:
            return (pool, logits, toks, emit, done, new_len, emitted,
                    RecurrentState(*st[0]))
        if counting:
            r_kle = rows[0]  # [K, Le, E]
            return (pool, logits, toks, emit, done, new_len, emitted, {
                "expert_rows": jnp.sum(r_kle),
                "expert_claims": jnp.sum(wvalid.astype(jnp.int32))
                * (cfg.experts_per_token * cfg.expert_layers),
                "expert_rows_max": jnp.sum(jnp.max(r_kle, axis=-1)),
                "experts_touched": jnp.sum((r_kle > 0).astype(jnp.int32)),
            })
        return pool, logits, toks, emit, done, new_len, emitted

    return jax.jit(
        window_fn, donate_argnums=(1, 2, 11) if hybrid else (1, 2)
    )


def make_prefill_chunk_program(
    model: GPT, *, chunk_len: int, pmax: int, rope_len: int, mesh=None,
    prefill_sp: str = "off",
):
    key = (
        "prefill_chunk", model.config, chunk_len, pmax, rope_len,
        prefill_sp, _mesh_key(mesh),
    )
    return _cached_program(
        key,
        lambda: _build_prefill_chunk_program(
            model.config, chunk_len=chunk_len, pmax=pmax,
            rope_len=rope_len, mesh=mesh, prefill_sp=prefill_sp,
        ),
    )


def _build_prefill_chunk_program(
    cfg, *, chunk_len: int, pmax: int, rope_len: int, mesh,
    prefill_sp: str = "off",
):
    """A prefill-chunk program for one padded chunk length: one forward
    over the chunk's tokens attending to the slot's already-resident
    pages (models.gpt.prefill_chunk_paged), a token-granular bulk page
    scatter, and the slot's logits row updated from the chunk's last
    real token — so the FINAL chunk of a prompt leaves exactly the
    logits a monolithic prefill would. Pool and logits are donated (the
    audit gates on it: a chunk runs between every pair of decode windows
    under chunked prefill, and an un-aliased pool would double KV HBM on
    the serving hot path). One compile per padded chunk length — the
    engine buckets chunks to powers-of-two page counts, and fixed-size
    chunking hits a single bucket in steady state."""
    from midgpt_tpu.parallel.sharding import axis_rules, shard_act

    assert chunk_len <= cfg.block_size, (chunk_len, cfg.block_size)

    def chunk_fn(
        model: GPT,  # entry parameter (same constant-folding trap as
        # the decode window — see make_decode_window)
        pool: PagedKVPool,  # DONATED
        logits: Array,  # [S, V] DONATED
        slot: Array,  # [] int32 — the prefilling slot
        tokens: Array,  # [1, chunk_len] int32 (right-padded)
        start: Array,  # [] int32 — absolute position of chunk token 0
        real_n: Array,  # [] int32 — real tokens in this chunk
        bt_row: Array,  # [pmax] int32 — the slot's block table
        state: tp.Optional[RecurrentState] = None,  # DONATED; a model with
        # linear-attention layers: returned last, the slot's rows advanced
        fresh: tp.Optional[Array] = None,  # [] bool — the request's first
        # chunk: the slot's state and tail start from zeros
    ):
        with axis_rules(mesh, serving_logical_rules(prefill_sp)):
            h, ks, vs, *new = prefill_chunk_paged(
                model, tokens, start, pool.k, pool.v, bt_row[None, :],
                rope_len, pool_sk=pool.scale_k, pool_sv=pool.scale_v,
                sp=(prefill_sp == "on"),
                block_len=cfg.block_len,
                **({} if state is None else {
                    "state": state.of_slot(slot, fresh), "real_n": real_n,
                }),
            )  # h: [1, T, D]; ks/vs: [L, 1, Hkv, T, C]
            if cfg.block_len:
                # a block-diffusion prompt's whole blocks leave K/V and
                # nothing else: its first block opens all masked, and no
                # logits row is carried (the head is 0.6 GB at 152k ids)
                return write_token_rows(
                    pool, ks[:, 0], vs[:, 0], bt_row, start, real_n
                ), logits
            h_last = jax.lax.dynamic_slice_in_dim(
                h, real_n - 1, 1, axis=1
            )[:, 0]  # [1, D]
            # vocab-sharded row update at vocab offset 0 (full-width on
            # the sharded dim: shard-local), keeping the donated logits
            # buffer on its committed sharding
            row = shard_act(model.project(h_last), None, "vocab")
            row = row.astype(logits.dtype)[0]
            logits = jax.lax.dynamic_update_slice(
                logits, row[None], (slot, jnp.zeros((), slot.dtype))
            )
            logits = shard_act(logits, None, "vocab")
            # page write AFTER the head projection (no data dependence
            # between them — a pure trace order): the lm head is the
            # trace's last weight projection in every serving program
            # (analysis.choreo takes the last one as the head), and an
            # int8 pool's page-birth quantization follows it
            pool = write_token_rows(
                pool, ks[:, 0], None if vs is None else vs[:, 0], bt_row,
                start, real_n,
            )
        if new:
            return pool, logits, state.with_slot(slot, *new[0])
        return pool, logits

    return jax.jit(
        chunk_fn, donate_argnums=(1, 2, 8) if cfg.linear_layers else (1, 2)
    )


def make_verify_program(
    model: GPT,
    *,
    slots: int,
    spec_len: int,
    pmax: int,
    rope_len: int,
    pad_id: int = 0,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
    soft_drafts: bool = False,
    mesh=None,
    paged_kernel: str = "xla",
):
    # temperature == 0.0 builds the exact greedy program (same signature,
    # same arithmetic — no seeds/key entry args); sampling params join
    # the cache key only as the knobs they are. soft_drafts (a proposer
    # that supplies a dense draft distribution — the injectable test
    # path) is a distinct program SHAPE: it adds a [S, spec_len, V]
    # entry tensor the default one-hot path deliberately never
    # materializes (see _build_verify_program).
    key = (
        "verify", model.config, slots, spec_len, pmax, rope_len, pad_id,
        temperature, top_k, soft_drafts, paged_kernel, _mesh_key(mesh),
    )
    return _cached_program(
        key,
        lambda: _build_verify_program(
            model.config, slots=slots, spec_len=spec_len, pmax=pmax,
            rope_len=rope_len, pad_id=pad_id, temperature=temperature,
            top_k=top_k, soft_drafts=soft_drafts, mesh=mesh,
            paged_kernel=paged_kernel,
        ),
    )


def _build_verify_program(
    cfg,
    *,
    slots: int,
    spec_len: int,
    pmax: int,
    rope_len: int,
    pad_id: int,
    mesh,
    paged_kernel: str = "xla",
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
    soft_drafts: bool = False,
):
    """The speculative-decoding verification program: ONE jitted,
    pool/logits-donating dispatch that scores every slot's
    ``[T = spec_len + 1]`` candidate rows (the true next token,
    materialized in-program from the carried logits, followed by the
    host's drafts) against the resident paged KV via
    ``models.gpt.verify_tokens_paged``, computes longest-prefix
    acceptance, EOS/budget truncation, and the per-slot WRITE WATERMARK,
    and folds only the accepted rows' K/V into the pages (one bulk
    scatter — rejected rows route to the drop sentinel, which IS the
    rollback: stale speculation never becomes visible to the pool, the
    prefix index, or another block table).

    At ``temperature == 0`` acceptance is greedy argmax agreement: draft
    row j is accepted iff it equals the argmax after row j-1 — chained
    from row 0, exactly the token the plain engine would have produced
    there, so greedy speculation is token-identical to the plain window.

    At ``temperature > 0`` acceptance is REJECTION SAMPLING, still in
    the same single dispatch: row 0 is drawn by the very
    ``sampling.sample_token`` the decode window uses, under the same
    per-request ``derive_request_key(key, seed, emitted)`` — so sampled
    row 0 is bitwise what the plain window's first step would have
    drawn. Draft row j (token t, draft probability q(t)) is accepted iff
    ``u_j * q(t) <= p(t)`` where ``p = target_probs(logits after row
    j-1)`` is the decode sampler's own distribution (softmax of the
    SAME tempered/top-k-masked logits ``sample_token`` draws from) and
    ``u_j`` is a uniform keyed by a SALTED substream of the position's
    derived key — a function of (request seed, stream position) only,
    never slot/window/batch, so sampled streams keep the greedy path's
    bitwise scheduling invariance. n-gram drafts carry one-hot draft
    probabilities (``q(t) = 1``, built in-program — no dense tensor
    crosses the dispatch boundary; see serving.speculate), collapsing
    the test to ``u <= p(t)``; a ``soft_drafts`` proposer ships a dense
    ``[S, spec_len, V]`` distribution instead (the injectable test path
    that exercises the general acceptance ratio).

    On rejection the program does NOT emit a resample token in-dispatch
    (the rejected row's K/V encodes the DRAFT token — emitting anything
    else would corrupt the pool). Instead the carried logits become
    ``temperature * log(normalize(max(p - q, 0)))`` — the residual
    distribution, encoded so the NEXT dispatch's ordinary row-0
    ``sample_token`` at that position's derived key IS the residual
    draw (``sampling.residual_logits`` documents the exactness
    argument). On full acceptance (or EOS/budget truncation) the carry
    is the last emitted row's raw logits, as in the greedy program —
    the next row-0 draw is then the standard speculative-sampling bonus
    token from the full target distribution. Either way every dispatch
    emits ``1 + accepted`` tokens and the stream is distributed exactly
    as the non-speculative sampled engine (classic speculative-sampling
    exactness, statistically tested in tests/test_serving.py; the
    acceptance/residual dtype choreography is proven by
    analysis.choreo's sampled-verify checks).

    Slot semantics mirror :func:`make_decode_window` exactly: done/empty
    slots ride along masked (pad candidates, no emissions, no writes),
    budget counts emitted tokens, an emitted EOS is kept and everything
    after it dropped, and a terminal token's K/V row is not written (no
    real token can follow it)."""
    from midgpt_tpu import sampling as sampling_mod
    from midgpt_tpu.parallel.sharding import axis_rules, shard_act
    from midgpt_tpu.sampling import (
        SPEC_ACCEPT_SALT,
        derive_request_key,
        residual_logits,
        sample_token,
        target_probs,
    )

    assert spec_len >= 1, spec_len
    assert not (soft_drafts and temperature == 0.0), (
        "soft_drafts is a sampled-verify program shape; greedy "
        "acceptance never reads draft probabilities"
    )
    t = spec_len + 1

    def _verify_core(
        model: GPT,  # ENTRY PARAMETER (constant-folding trap, see
        # make_decode_window)
        pool: PagedKVPool,  # DONATED
        logits: Array,  # [S, V] f32 — per-slot next-token logits; DONATED
        bt: Array,  # [S, Pmax] int32 block tables
        pooled_len: Array,  # [S] int32 — write watermark (tokens resident)
        done: Array,  # [S] bool — finished or empty slot
        emitted: Array,  # [S] int32 — tokens emitted so far per request
        budget: Array,  # [S] int32 — max_new_tokens per request
        eos: Array,  # [S] int32 — per-request EOS id (-1 = none)
        drafts: Array,  # [S, spec_len] int32 — host n-gram drafts
        n_draft: Array,  # [S] int32 in [0, spec_len] — per-slot draft len
        seeds: tp.Optional[Array] = None,  # [S] int32 (sampled only)
        key: tp.Optional[Array] = None,  # base PRNG key (sampled only)
        draft_probs: tp.Optional[Array] = None,  # [S, spec_len, V]
        # (soft_drafts only) — the dense draft distribution
    ):
        assert bt.shape == (slots, pmax), (
            f"block table {bt.shape} != declared geometry ({slots}, {pmax})"
        )
        with axis_rules(mesh, serving_logical_rules()):
            # row 0: the true next token, materialized from the carried
            # logits — the same decision the plain window's step 0 takes
            # from the same logits (argmax at T=0, sample_token under
            # the position's derived key at T>0)
            if temperature == 0.0:
                t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                ks0 = jax.vmap(
                    lambda sd, ti: derive_request_key(key, sd, ti)
                )(seeds, emitted)
                t0 = jax.vmap(
                    lambda l1, k1: sample_token(
                        l1[None], k1, temperature, top_k
                    )[0]
                )(logits, ks0)
            t0 = jnp.where(done, jnp.int32(pad_id), t0)
            cand = jnp.concatenate([t0[:, None], drafts], axis=1)  # [S, T]
            all_logits, ks, vs = verify_tokens_paged(
                model, cand, pooled_len, pool.k, pool.v, bt, rope_len,
                pool_sk=pool.scale_k, pool_sv=pool.scale_v,
                paged_kernel=paged_kernel,
            )  # all_logits: [S, T, V]; ks/vs: [L, S, Hkv, T, C]
            if temperature == 0.0:
                preds = jnp.argmax(all_logits, axis=-1).astype(jnp.int32)
                # draft row j (cand[:, j], j >= 1) matches iff it equals
                # the model's argmax after row j-1 and sits within the
                # slot's draft length; acceptance is the longest
                # matching PREFIX
                match = (cand[:, 1:] == preds[:, :-1]) & (
                    jnp.arange(spec_len)[None, :] < n_draft[:, None]
                )
                p = qf = None
            else:
                # the target distribution after each prefix row — BY
                # CONSTRUCTION what sample_token draws from at this
                # (temperature, top_k); f32 throughout (the acceptance
                # compare is the sampled path's near-tie surface, pinned
                # by the choreo prover)
                p = target_probs(
                    all_logits[:, :-1], temperature, top_k
                )  # [S, spec_len, V] f32
                p_sel = jnp.take_along_axis(
                    p, cand[:, 1:, None], axis=2
                )[..., 0]  # [S, spec_len] — p(draft token)
                if soft_drafts:
                    qf = draft_probs.astype(jnp.float32)
                    q_sel = jnp.take_along_axis(
                        qf, cand[:, 1:, None], axis=2
                    )[..., 0]
                else:
                    # one-hot n-gram drafts: q(draft token) = 1 — built
                    # in-program so no [S, spec_len, V] tensor crosses
                    # the dispatch boundary (the verify program's
                    # traffic budget cells stay exactly as greedy)
                    qf = None
                    q_sel = jnp.ones((slots, spec_len), jnp.float32)
                # acceptance uniforms: one per (request, stream
                # position), keyed by a salted substream of the
                # position's derived key — independent of the
                # categorical stream (a rejection at position i must
                # resample with position i's untouched categorical key)
                # and invariant to slot/window/batch/eviction
                pos = emitted[:, None] + jnp.arange(
                    1, spec_len + 1, dtype=jnp.int32
                )[None, :]
                u = jax.vmap(
                    jax.vmap(
                        lambda sd, ti: jax.random.uniform(
                            jax.random.fold_in(
                                derive_request_key(key, sd, ti),
                                SPEC_ACCEPT_SALT,
                            ),
                            (),
                            jnp.float32,
                        ),
                        in_axes=(None, 0),
                    )
                )(seeds, pos)  # [S, spec_len] f32
                match = sampling_mod.acceptance_mask(u, q_sel, p_sel) & (
                    jnp.arange(spec_len)[None, :] < n_draft[:, None]
                )
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1) > 0
            ok = jnp.concatenate(
                [jnp.ones((slots, 1), bool), acc], axis=1
            )  # [S, T] — row 0 always a real emission for a live slot
            allowed = budget - emitted  # >= 1 for any live slot
            ok = ok & (jnp.arange(t)[None, :] < allowed[:, None])
            ok = ok & ~done[:, None]
            # an emitted EOS is kept; every row after it is dropped
            is_eos = ok & (cand == eos[:, None])
            eos_before = (
                jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                - is_eos.astype(jnp.int32)
            ) > 0
            emit = ok & ~eos_before  # [S, T] — always a contiguous prefix
            n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)  # [S]
            new_emitted = emitted + n_emit
            hit_eos = jnp.any(emit & (cand == eos[:, None]), axis=1)
            new_done = done | hit_eos | (new_emitted >= budget)
            # write watermark: every emitted row's K/V is true context —
            # except a terminal row (EOS/budget), which no token follows
            # (same write_valid discipline as the decode window)
            n_write = n_emit - (new_done & ~done).astype(jnp.int32)
            n_write = jnp.maximum(n_write, 0)
            wvalid = jnp.arange(t)[None, :] < n_write[:, None]  # [S, T]
            pool = flush_recent(pool, ks, vs, bt, pooled_len, wvalid)
            new_len = pooled_len + n_write
            # carried logits: after the last emitted row (exact — its
            # whole prefix was accepted); done slots take row 0, which is
            # scratch until an admission overwrites the row. f32 widening
            # is exact, same as the decode window's carry.
            last = jnp.clip(n_emit - 1, 0, t - 1)
            base = jnp.take_along_axis(
                all_logits, last[:, None, None], axis=1
            )[:, 0]
            # accepted = drafts the MODEL agreed with (pre-EOS/budget
            # truncation): the honest acceptance signal for adaptation —
            # end-of-generation budget clipping is not a drafting miss
            n_acc = jnp.sum(acc.astype(jnp.int32), axis=1)
            if temperature == 0.0:
                new_logits = base.astype(logits.dtype)
            else:
                # rejection carry: when the emission prefix stopped at a
                # REJECTED draft (not EOS/budget truncation), the next
                # row-0 draw must come from the residual distribution
                # max(p - q, 0) at the rejected position — encoded as
                # logits so the next dispatch's ordinary sample_token at
                # that position's derived key IS the residual draw (see
                # sampling.residual_logits for the exactness argument).
                rej = jnp.clip(n_acc, 0, spec_len - 1)  # first rejected row
                p_carry = jnp.take_along_axis(
                    p, rej[:, None, None], axis=1
                )[:, 0]  # [S, V] — target probs at the rejected position
                if soft_drafts:
                    q_carry = jnp.take_along_axis(
                        qf, rej[:, None, None], axis=1
                    )[:, 0]
                else:
                    d_rej = jnp.take_along_axis(
                        drafts, rej[:, None], axis=1
                    )[:, 0]
                    q_carry = jax.nn.one_hot(
                        d_rej, cfg.vocab_size, dtype=jnp.float32
                    )
                resid_lg, mass = residual_logits(
                    p_carry, q_carry, temperature
                )
                # residual only when the prefix genuinely ended at a
                # rejection: some draft was rejected (n_acc < n_draft)
                # AND no EOS/budget clip shortened the prefix first
                # (n_emit == 1 + n_acc) AND the residual has mass (a
                # one-hot q fully inside p's top-k support can zero it —
                # then p == q at that token was impossible to reject,
                # but guard anyway and fall back to the full target)
                use_resid = (
                    (n_acc < n_draft)
                    & (n_emit == n_acc + 1)
                    & (mass > 0.0)
                )
                new_logits = jnp.where(
                    use_resid[:, None], resid_lg,
                    base.astype(jnp.float32),
                ).astype(logits.dtype)
            # the take_along_axis indexes the (replicated) row dim of a
            # vocab-sharded [S, T, V]; pin the carry so the donated
            # logits buffer keeps its committed sharding
            new_logits = shard_act(new_logits, None, "vocab")
        return (
            pool, new_logits, cand, emit, new_done, new_len, new_emitted,
            n_acc,
        )

    # the greedy wrapper keeps the pre-sampled 11-arg signature (and
    # arithmetic) byte-for-byte: existing greedy budgets, audits, and
    # bitwise stream tests see the exact same program. The sampled
    # shapes append only [S] seeds + the base key (control-stream
    # traffic) — and, for the soft-draft test variant, the dense draft
    # distribution.
    if temperature == 0.0:
        def verify_fn(
            model, pool, logits, bt, pooled_len, done, emitted, budget,
            eos, drafts, n_draft,
        ):
            return _verify_core(
                model, pool, logits, bt, pooled_len, done, emitted,
                budget, eos, drafts, n_draft,
            )
    elif not soft_drafts:
        def verify_fn(
            model, pool, logits, bt, pooled_len, done, emitted, budget,
            eos, drafts, n_draft, seeds, key,
        ):
            return _verify_core(
                model, pool, logits, bt, pooled_len, done, emitted,
                budget, eos, drafts, n_draft, seeds=seeds, key=key,
            )
    else:
        def verify_fn(
            model, pool, logits, bt, pooled_len, done, emitted, budget,
            eos, drafts, n_draft, seeds, key, draft_probs,
        ):
            return _verify_core(
                model, pool, logits, bt, pooled_len, done, emitted,
                budget, eos, drafts, n_draft, seeds=seeds, key=key,
                draft_probs=draft_probs,
            )

    return jax.jit(verify_fn, donate_argnums=(1, 2))


def make_block_window(
    model: GPT,
    *,
    slots: int,
    window: int,
    pmax: int,
    rope_len: int,
    mesh=None,
    paged_kernel: str = "xla",
):
    key = (
        "block_window", model.config, slots, window, pmax, rope_len,
        paged_kernel, _mesh_key(mesh),
    )
    return _cached_program(
        key,
        lambda: _build_block_window(
            model.config, slots=slots, window=window, pmax=pmax,
            rope_len=rope_len, mesh=mesh, paged_kernel=paged_kernel,
        ),
    )


def _build_block_window(
    cfg, *, slots: int, window: int, pmax: int, rope_len: int, mesh,
    paged_kernel: str = "xla",
):
    """The decode window of a BLOCK-DIFFUSION model (``cfg.block_len`` =
    B > 0): ONE jitted, pool-donating ``lax.scan`` over ``window``
    forwards, each a forward of 2·B rows a slot through
    ``models.gpt.verify_tokens_paged`` under the block mask — the verify
    program's forward with another mask, not another one.

    A slot's unit of work is not a token. Its block state is
    (``tok`` [B] the block's tokens, the mask token where nothing is
    revealed yet; ``rev`` [B] the revealed set, kept explicitly — with
    random weights the argmax IS the mask token once in V tokens, and a
    comparison of ids would read that as still masked; ``at`` [B] the
    denoising step at which each position was revealed, -1 for a prompt
    position and for one not yet revealed) and the block before it where
    that is complete and not yet resident (``pend`` and ``pend_tok`` [B],
    its final tokens). The published loop spends a forward of its own on
    a complete block to write its K/V; here that COMMIT rides with the
    next block's first denoising forward. The rows of a forward are always
    the positions ``pooled_len + [0, 2B)``:

    - with a block pending, ``[pend_tok | tok]``: under the block mask the
      pending block's rows see the context and themselves — what a commit
      forward computes — and land in the pages (``flush_recent``; the
      resident length grows by B, ``pend`` clears), while the current
      block's rows see the context, the pending block's FINAL tokens and
      themselves: what they would see one forward after the commit;
    - with none, ``[tok | mask tokens]``: the second half is dead rows,
      which the first half does not see, which claim no expert and whose
      results nobody reads.

    Every forward of a slot in flight is a DENOISING forward of its
    current block (a block in flight always has a masked position): the
    head runs on those B rows only; at every masked position the greedy
    pick and its confidence (``sampling.token_confidence``), and the ``B //
    block_steps`` masked positions of highest confidence are revealed
    (``sampling.reveal_most_confident``: ``low_confidence_static`` at
    temperature 0). Logits of row i predict position i itself. When the
    last position is revealed the block's generated tokens are emitted (a
    prompt's ``P mod B`` remainder opens the first block already revealed
    and is not emitted), up to the request's budget; if the request goes
    on, the block becomes the pending one and a fresh all-masked block
    begins. The block that ends a request is not committed: no token
    follows it.

    So a block costs ``block_steps`` forwards, and ``tokens`` grow by
    whole blocks. Done and empty slots ride along, all their rows dead.
    Returns the pool, the slots' state after the window, and per forward
    what the host harvests: the blocks as they stood, the forwards that
    completed one and how many of its tokens count, plus the window's
    counters (slot-forwards, those that also landed a block, positions
    revealed, and — for an ExpertMLP model — the rows routed to each
    expert of each layer)."""
    from midgpt_tpu.parallel.sharding import axis_rules
    from midgpt_tpu.sampling import reveal_most_confident, token_confidence

    blk, n_steps = cfg.block_len, cfg.block_steps
    assert blk >= 1 and n_steps >= 1 and blk % n_steps == 0, (blk, n_steps)
    n_reveal = blk // n_steps
    mask_tok = jnp.int32(cfg.mask_token)
    with_rows = cfg.mlp == "experts"

    def window_fn(
        model: GPT,  # ENTRY PARAMETER (see make_decode_window)
        pool: PagedKVPool,  # DONATED
        bt: Array,  # [S, Pmax] int32 block tables
        pooled_len: Array,  # [S] int32 — committed tokens, a multiple of B
        done: Array,  # [S] bool — finished or empty slot
        emitted: Array,  # [S] int32 — tokens emitted so far per request
        budget: Array,  # [S] int32 — max_new_tokens per request
        eos: Array,  # [S] int32 — per-request EOS id (-1 = none)
        tok: Array,  # [S, B] int32 — the current block
        rev: Array,  # [S, B] bool — its revealed set
        at: Array,  # [S, B] int32 — reveal step per position
        pend: Array,  # [S] bool — the block before it is yet to land
        pend_tok: Array,  # [S, B] int32 — that block's final tokens
    ):
        assert bt.shape == (slots, pmax), (
            f"block table {bt.shape} != declared geometry ({slots}, {pmax})"
        )
        with axis_rules(mesh, serving_logical_rules()):

            def body(carry, _):
                (pool, pooled_len, done, emitted, tok, rev, at, pend,
                 pend_tok) = carry
                denoise = ~done
                land = denoise & pend
                wide = jnp.broadcast_to(land[:, None], tok.shape)
                rows = jnp.concatenate(
                    (jnp.where(wide, pend_tok, tok),
                     jnp.where(wide, tok, mask_tok)), axis=1,
                )  # [S, 2B]
                live = jnp.concatenate(
                    (jnp.broadcast_to(denoise[:, None], tok.shape), wide),
                    axis=1,
                )
                out = verify_tokens_paged(
                    model, rows, pooled_len, pool.k, pool.v, bt, rope_len,
                    paged_kernel=paged_kernel,
                    block_len=blk, expert_rows=with_rows, live=live,
                    head_block=land.astype(jnp.int32),
                )
                logits, ks, vs = out[:3]  # logits: the current block's
                with jax.named_scope("block_reveal"):
                    pick, conf = token_confidence(logits)  # [S, B]
                    chosen = reveal_most_confident(
                        conf, ~rev, n_reveal
                    ) & denoise[:, None]
                    step = jnp.max(at, axis=1) + 1  # this block's forward no.
                    tok = jnp.where(chosen, pick, tok)
                    rev = rev | chosen
                    at = jnp.where(chosen, step[:, None], at)
                    complete = denoise & jnp.all(rev, axis=1)
                    # the block's generated positions are its tail (a
                    # prompt remainder is its head); the first n_emit
                    # of them are the request's, the rest is past budget
                    gen = at >= 0
                    n_emit = jnp.where(
                        complete,
                        jnp.minimum(
                            jnp.sum(gen.astype(jnp.int32), axis=1),
                            budget - emitted,
                        ),
                        0,
                    )
                    rank = jnp.cumsum(gen.astype(jnp.int32), axis=1) - 1
                    counted = gen & (rank < n_emit[:, None])
                    hit_eos = jnp.any(counted & (tok == eos[:, None]), axis=1)
                    emitted = emitted + n_emit
                    done = done | (
                        complete & ((emitted >= budget) | hit_eos)
                    )
                # the pending block's B rows land (the first half)
                pool = flush_recent(
                    pool, ks[:, :, :, :blk], vs[:, :, :, :blk], bt,
                    pooled_len, wide,
                )
                pooled_len = pooled_len + jnp.where(land, blk, 0)
                ys = (
                    tok, at, complete, n_emit, denoise, land,
                    jnp.sum(chosen.astype(jnp.int32)),
                ) + ((out[3],) if with_rows else ())
                # a complete block of a request that goes on is the
                # pending one from here, and a fresh block begins
                move = complete & ~done
                fresh = move[:, None]
                pend = jnp.where(denoise, move, pend)
                pend_tok = jnp.where(fresh, tok, pend_tok)
                tok = jnp.where(fresh, mask_tok, tok)
                rev = rev & ~fresh
                at = jnp.where(fresh, -1, at)
                return (pool, pooled_len, done, emitted, tok, rev, at, pend,
                        pend_tok), ys

            carry, ys = jax.lax.scan(
                body,
                (pool, pooled_len, done, emitted, tok, rev, at, pend,
                 pend_tok),
                None, length=window,
            )
            toks, ats, complete, n_emit, denoise, land, revealed = ys[:7]
            counters = {
                "denoise_forwards": jnp.sum(denoise.astype(jnp.int32)),
                "commit_forwards": jnp.sum(land.astype(jnp.int32)),
                "tokens_revealed": jnp.sum(revealed),
            }
            if with_rows:
                rows = ys[7]  # [K, L, E]
                counters["expert_rows"] = jnp.sum(rows, axis=0)  # [L, E]
                counters["expert_rows_max"] = jnp.sum(jnp.max(rows, axis=-1))
                counters["experts_touched"] = jnp.sum(
                    (rows > 0).astype(jnp.int32)
                )
        return carry, (toks, ats, complete, n_emit), counters

    return jax.jit(window_fn, donate_argnums=(1,))


def trace_serving_programs(
    model: GPT,
    *,
    slots: int = 4,
    window: int = 4,
    spec_len: int = 4,
    chunk_len: int = 64,
    page_size: int = 16,
    num_pages: tp.Optional[int] = None,
    mesh=None,
    kv_quant: tp.Optional[str] = None,
    paged_kernel: str = "xla",
    prefill_sp: str = "off",
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
) -> tp.Dict[str, tp.Any]:
    """Abstractly trace the engine's three hot-path programs to jaxprs —
    the input of the arithmetic-choreography prover
    (:mod:`midgpt_tpu.analysis.choreo`). Returns
    ``{"decode_window": ClosedJaxpr, "prefill_chunk": ..., "verify": ...}``.
    ``temperature > 0`` traces the SAMPLED decode window and the
    rejection-sampling verify program (its signature grows the per-slot
    seeds + base key the sampled acceptance derives its streams from).

    Tracing goes through the very same jitted callables the engine
    launches (:func:`make_decode_window` et al.), so the prover sees the
    program the hardware runs — model as an entry parameter, the fused
    window scan, the in-program sampling/acceptance glue — not a
    hand-maintained replica of it. No compilation, no execution: a full
    three-program trace takes seconds on CPU at audit size."""
    from midgpt_tpu.serving.paged import pages_needed

    cfg = model.config
    pmax = pages_needed(cfg.block_size, page_size)
    if num_pages is None:
        num_pages = slots * pmax
    pool = jax.eval_shape(
        lambda: PagedKVPool.init(cfg, num_pages, page_size,
                                 kv_quant=kv_quant)
    )
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    logits = sds((slots, cfg.vocab_size), f32)
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    pred = lambda *s: sds(s, jnp.bool_)  # noqa: E731

    window_fn = make_decode_window(
        model, slots=slots, window=window, pmax=pmax,
        rope_len=cfg.block_size, temperature=temperature, top_k=top_k,
        mesh=mesh, paged_kernel=paged_kernel,
    )
    decode_jaxpr = jax.make_jaxpr(window_fn)(
        model, pool, logits, i32(slots, pmax), i32(slots), pred(slots),
        i32(slots), i32(slots), i32(slots), i32(slots),
        sds((2,), jnp.uint32),
    )
    chunk_fn = make_prefill_chunk_program(
        model, chunk_len=chunk_len, pmax=pmax, rope_len=cfg.block_size,
        mesh=mesh, prefill_sp=prefill_sp,
    )
    chunk_jaxpr = jax.make_jaxpr(chunk_fn)(
        model, pool, logits, i32(), i32(1, chunk_len), i32(), i32(),
        i32(pmax),
    )
    verify_fn = make_verify_program(
        model, slots=slots, spec_len=spec_len, pmax=pmax,
        rope_len=cfg.block_size, temperature=temperature, top_k=top_k,
        mesh=mesh, paged_kernel=paged_kernel,
    )
    verify_args = [
        model, pool, logits, i32(slots, pmax), i32(slots), pred(slots),
        i32(slots), i32(slots), i32(slots), i32(slots, spec_len),
        i32(slots),
    ]
    if temperature > 0.0:
        verify_args += [i32(slots), sds((2,), jnp.uint32)]
    verify_jaxpr = jax.make_jaxpr(verify_fn)(*verify_args)
    return {
        "decode_window": decode_jaxpr,
        "prefill_chunk": chunk_jaxpr,
        "verify": verify_jaxpr,
    }


def make_copy_page_program():
    """The jitted copy-on-write primitive: duplicate one page so an
    admission landing on a partially-shared cached page gets a private
    copy to append into. Pool donated — the copy is in-place up to the
    one written page row. One shared wrapper (program cache): copy_page
    is model-free, so every engine reuses the same jit cache."""
    return _cached_program(
        ("copy_page",), lambda: jax.jit(copy_page, donate_argnums=(0,))
    )


# ---------------------------------------------------------------------------
# Requests + engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record."""

    rid: int
    prompt: np.ndarray  # [p] int32 admission context (original prompt, or
    # prompt0 + generated-so-far after an eviction re-queue)
    max_new_tokens: int
    # the cropped ORIGINAL prompt — evictions rebuild the admission
    # context from this, never from an already-grown prompt
    prompt0: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32)
    )
    eos_id: int = -1  # -1 = no EOS (run to max_new_tokens)
    seed: int = 0
    # the engine clock's stamps of the time to the first token, kept
    # whether anything traces or not: submitted, first given a slot, the
    # prompt resident (the last time before the first token, should an
    # eviction make it resident twice), first token harvested
    submit_time: float = 0.0
    admit_time: tp.Optional[float] = None
    prefill_done_time: tp.Optional[float] = None
    first_token_time: tp.Optional[float] = None
    finish_time: tp.Optional[float] = None
    tokens: tp.List[int] = dataclasses.field(default_factory=list)
    # block-diffusion engines: per token, the denoising step of its block
    # at which it was revealed (empty otherwise)
    reveal_steps: tp.List[int] = dataclasses.field(default_factory=list)
    evictions: int = 0
    cached_tokens: int = 0  # prompt tokens served from the prefix cache
    # (summed over admissions — re-admissions typically re-hit)
    # speculative decoding (engine speculate > 0): current adaptive draft
    # length, trailing acceptance EWMA, and lifetime draft accounting.
    # spec_k survives eviction/re-admission — the controller state is a
    # property of the request's text, not the slot it lands in.
    spec_k: int = 0
    spec_rate: float = 1.0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # overload bookkeeping: tokens held at the LAST admission (progress
    # detector) and the count of consecutive evictions with zero progress
    # since then — the eviction-livelock guard parks the request at
    # ``park_threshold`` (two requests thrashing each other's pages
    # would otherwise re-prefill in a loop instead of one waiting)
    admit_tokens: int = 0
    thrash: int = 0
    # scheduling (the front-door admission policy, replacing FIFO):
    # higher ``priority`` dispatches first; ``deadline`` is an ABSOLUTE
    # engine-clock instant past which a still-undispatched request is
    # shed (None = no SLO). queue_seq/queue_step are the scheduler's
    # tie-break and aging bookkeeping: seq is the submission order
    # within a priority band, step the scheduler step of the last
    # enqueue (effective priority grows by ``priority_aging`` per step
    # queued — the starvation-freedom mechanism).
    priority: int = 0
    deadline: tp.Optional[float] = None
    queue_seq: int = 0
    queue_step: int = 0
    # terminal outcome: "pending" while live, then one of
    # "finished" | "cancelled" | "expired" (deadline shed)
    outcome: str = "pending"

    @property
    def done(self) -> bool:
        return self.finish_time is not None


@dataclasses.dataclass
class HandoffRecord:
    """A fully-prefilled request packaged for the prefill→decode page
    handoff (serving.cluster disaggregated pools): the live
    :class:`Request`, its context tokens, the block-table-addressed page
    payloads (+ int8 scale planes) as host arrays, and the CARRIED
    LOGITS ROW — exactly the row the final prefill chunk wrote, which is
    what a monolithic engine would decode its first token from, so the
    importing decode engine resumes the stream bit-identically. Built by
    :meth:`ServingEngine.export_request`, consumed by
    :meth:`ServingEngine.import_request`; everything here is host state,
    so the record crosses engines (and, in a multi-host deployment, the
    DCN wire) with no device aliasing."""

    req: Request
    ctx: tp.List[int]  # the slot's context tokens (== the prompt)
    resident: int  # pool-resident tokens (== len(ctx))
    logits_row: np.ndarray  # [V] f32 — the final prefill chunk's row
    n_pages: int
    k: np.ndarray  # [L, n_pages, PS, Hkv*C] pool dtype
    v: np.ndarray
    sk: tp.Optional[np.ndarray]  # [L, n_pages, Hkv] f32 (int8 pools)
    sv: tp.Optional[np.ndarray]

    @property
    def nbytes(self) -> int:
        """Handoff wire bytes (payload + scales + logits) — what
        ``serve_handoff_bytes`` accounts."""
        n = self.k.nbytes + self.v.nbytes + self.logits_row.nbytes
        if self.sk is not None:
            n += self.sk.nbytes + self.sv.nbytes
        return int(n)


class _Flight(tp.NamedTuple):
    """A decode window enqueued and not yet read: its dispatch span, the
    slots it carries, its device outputs (tokens, emit mask, ``done``,
    lengths, ``emitted``, a latent model's counters), and whether it went
    in before the window in front of it was read."""

    disp: span
    decoding: tp.List[int]
    out: tp.List[tp.Any]
    ahead: bool


# Registry-backed counter attributes of ServingEngine: every name here
# becomes a class-level property reading/writing the engine's
# MetricsRegistry Counter of the same name (attached right after the
# class body). The registry is the single source of truth; stats() and
# the metrics snapshot are two views of it.
_ENGINE_COUNTERS = (
    "decode_dispatches",
    "device_reads",
    "prefill_dispatches",
    "prefill_chunks",
    "prefill_steps",
    "copy_dispatches",
    "tokens_generated",
    "windows",
    "occupancy_sum",
    "kv_pages_walked",
    "kv_pages_table",
    "kv_pages_distinct",
    "evictions",
    "prompt_tokens_total",
    "prompt_tokens_cached",
    "prefill_tokens_computed",
    "cold_reclaims",
    "spilled_pages",
    "spill_faultback_pages",
    "spill_prefetch_pages",
    "spill_readmissions",
    "spill_discards",
    "verify_dispatches",
    "spec_drafted",
    "spec_accepted",
    "admission_rejected",
    "shed_requests",
    "deferred_submits",
    "livelock_parks",
    "overload_parks",
    "cancelled_requests",
    "deadline_shed_requests",
    "faults_injected",
    # block-diffusion windows (all zero otherwise): slot-forwards (each
    # denoises a block), those of them that also landed the block before
    # it, blocks whose K/V landed, positions revealed, and of the 2·B rows
    # a slot-forward runs those that were a token's; and of an expert
    # model's window forwards, per layer and forward: rows routed, rows
    # not computed (always 0: the layer is dropless), the busiest
    # expert's rows, experts with a row, and how many (layer, forward)s
    "denoise_forwards",
    "commit_forwards",
    "blocks_committed",
    "tokens_revealed",
    "block_rows_live",
    "block_rows_run",
    "expert_rows_routed",
    "expert_rows_dropped",
    "expert_rows_max",
    "experts_touched",
    "expert_layer_forwards",
    # a model with recurrent layers (all zero otherwise): admissions
    # (each starts its slot's recurrent state from zeros), tokens prefilled
    # again after an eviction because no snapshot of the state exists,
    # admissions whose first page the prefix cache had seen (and could not
    # serve), and slot x recurrent layer x decode step
    "state_resets",
    "state_reprefill_tokens",
    "prefix_hits_refused",
    "recurrent_slot_steps",
    # a model with Mamba layers (zero otherwise): the prefill chunks' rows
    # that were a prompt's, and the rows their programs ran (the bucket's:
    # the scan walks its padding too)
    "ssm_rows_scanned",
    "ssm_rows_run",
    # decode windows enqueued before the window in front of them was read
    # (ServingEngine._ahead): the engaged share is windows_ahead /
    # decode_dispatches
    "windows_ahead",
)


def _counter_property(name: str) -> property:
    def _get(self):
        return self.metrics.counter(name).value

    def _set(self, v):
        self.metrics.counter(name).value = v

    return property(
        _get, _set, doc=f"registry-backed counter {name!r} "
        "(serving.telemetry.MetricsRegistry)"
    )


# What each kind of model has no engine form for yet, refused by
# ServingEngine at construction: kind -> (the subject of the refusal, the
# features it refuses). A recurrent layer's state has no snapshot at an
# earlier position and is not sharded; the latent pool and the block
# forward have no form for the rest yet (ROADMAP Reach).
_REFUSED: tp.Dict[str, tp.Tuple[str, tp.Tuple[str, ...]]] = {
    "recurrent": ("a model with recurrent layers", (
        "temperature > 0", "speculate", "quant", "kv_quant", "a mesh",
        "role != 'both'", "spill", "prefill_sp", "block_len",
    )),
    "latent": ("a model with latent attention", (
        "speculate", "quant", "kv_quant", "a mesh", "role != 'both'",
        "spill", "prefill_sp", "block_len",
    )),
    "block": ("block-diffusion generation", (
        "temperature > 0", "speculate", "kv_quant", "quant", "a mesh",
        "role != 'both'", "spill",
    )),
}


class ServingEngine:
    """Continuous-batching scheduler over ``slots`` decode lanes.

    Every :meth:`step` is one scheduler window: admit queued requests
    into free slots (prefix-cache match + page allocation), run up to
    ``prefill_budget`` tokens of pending prefill chunks, top up page
    allocations for the coming K tokens (evicting the youngest request
    under pressure — its progress is kept and it re-queues with
    prompt+generated), launch ONE fused K-step decode dispatch for all
    decoding slots, then harvest emitted tokens / finished requests with
    a single device->host read.

    What crosses between host and device in a step, once each: a prefill
    dispatch PUTS its tokens, three scalars and a copy of the slot's
    block-table row in one ``jax.device_put`` and reads nothing back
    (:meth:`_dispatch_chunk`); a window — decode, verify or block — PUTS
    the scheduler's host arrays in one ``jax.device_put`` and READS its
    results in one ``jax.device_get`` (:meth:`_read_window`, counted as
    ``device_reads``: over any span of steps it equals
    ``decode_dispatches``). Pool, logits and a recurrent state never
    leave the device.

    A decode window goes in one window ahead of its read wherever no slot
    can end in the window in front of it (no EOS id, more than ``window``
    tokens of budget left), every slot decodes, nobody waits for a slot
    and the pages are there (:meth:`_ahead`): it takes that window's
    lengths, ``done`` and ``emitted`` as they are on the device, and the
    host's read, bookkeeping, caller and scheduling then run while it
    computes. Its tokens are those of the plain order; ``windows_ahead``
    counts such windows.

    Prefix cache (``prefix_cache=True``): full pages are registered in a
    host-side content index as they fill; admission points the block
    table at matched pages (skipping their prefill compute entirely),
    copies the one partially-matched page (COW), and computes only the
    suffix — always at least the last prompt token, which is what
    produces the first decode logits. Finished requests' pages stay
    resident cold until page pressure reclaims them LRU. Token streams
    are identical with the cache on or off.

    Chunked prefill (``prefill_chunk=N``): N tokens is the granule of the
    prefill budget, at most ``prefill_budget`` tokens between consecutive
    decode windows, handed out round-robin over the prefilling slots. In
    front of a K-step decode window the budget defaults to one granule a
    step (``N * window``), so each token the window emits waits behind at
    most one granule's prefill; in front of a verify dispatch or a block
    window (whose ``window`` counts forwards, not token steps) it stays
    one granule. A slot's granules of one step run as ONE chunk program
    of at most ``max(N, PREFILL_MERGE_ROWS)`` rows (past that, a next
    one), so the weights are streamed once for them and not once a
    granule. ``prefill_chunk=None`` keeps the monolithic behavior (the
    whole uncached suffix in one dispatch).

    Self-speculative decoding (``speculate=N``, greedy only): every
    decode dispatch becomes a VERIFY dispatch — a host-side n-gram
    proposer (``serving.speculate.NgramProposer``, injectable via
    ``proposer=``) drafts up to N tokens per request from its own
    history, and one jitted program scores the ``N+1`` candidate rows of
    every slot against the resident pages, emitting ``1 + accepted``
    tokens per slot per dispatch. Draft length adapts per request to its
    trailing acceptance rate. Rejected rows' K/V never lands (the write
    scatter is masked at the per-slot watermark), so allocator/index
    invariants are untouched and greedy output is token-identical to
    ``speculate=0``.

    Capacity contract: a request must fit its context in ``block_size``
    (prompts are cropped to ``block_size - max_new_tokens`` like the
    reference sampler crops to the window, sample.py:74).

    Overload and faults degrade, they don't crash (serving.faults):
    unservable submissions raise typed, counted ``AdmissionRejected``;
    a full bounded queue (``max_queue``) sheds or defers per
    ``overload_policy``; pool pressure a lone request can't evict its
    way out of PARKS the request (progress kept) instead of raising
    ``MemoryError``; and the eviction-livelock guard parks a request
    evicted ``park_threshold`` times without progress. A scripted
    ``fault_hook`` (``FaultPlan.hook``) injects deterministic chaos at
    step boundaries; ``drain_requests``/``resubmit`` are the cluster's
    failover seam, and every degraded path preserves the bit-identical
    stream contract above.
    """

    # False forces the plain order, every window read before the next is
    # dispatched (tests compare the two)
    _dispatch_ahead = True

    def __init__(
        self,
        model: GPT,
        *,
        slots: int = 4,
        page_size: int = 16,  # tile-aligned at C=64; same default everywhere
        num_pages: tp.Optional[int] = None,
        window: int = 4,
        temperature: float = 0.0,
        top_k: tp.Optional[int] = None,
        cache_dtype=jnp.bfloat16,
        pad_id: int = 0,
        seed: int = 0,
        max_prefills_per_window: tp.Optional[int] = None,
        prefix_cache: bool = True,
        prefill_chunk: tp.Optional[int] = None,
        prefill_budget: tp.Optional[int] = None,
        speculate: int = 0,
        proposer: tp.Optional[Proposer] = None,
        quant: tp.Optional[str] = None,
        kv_quant: tp.Optional[str] = None,
        paged_kernel: str = "auto",
        prefill_sp: str = "auto",
        spill: str = "off",
        spill_budget_pages: tp.Optional[int] = None,
        spill_prefetch: str = "on",
        mesh=None,
        clock: tp.Callable[[], float] = time.monotonic,
        max_queue: tp.Optional[int] = None,
        overload_policy: str = "defer",
        park_threshold: int = 2,
        priority_aging: float = 0.125,
        fault_hook: tp.Optional[tp.Callable[["ServingEngine"], None]] = None,
        telemetry: tp.Union[None, bool, EngineTelemetry] = None,
        role: str = "both",
    ):
        assert slots >= 1 and window >= 1 and page_size >= 1
        # replica class (serving.cluster disaggregated pools): "both" is
        # the monolithic engine; "prefill" runs chunked prefill to
        # completion and then PARKS the slot handoff-ready (it never
        # decodes — the cluster exports the pages to a decode-class
        # engine); "decode" is a routing label only — the engine is a
        # full engine (eviction re-queues must re-prefill locally, which
        # is what keeps post-handoff eviction bit-identical), the
        # cluster just never routes fresh submissions at it.
        assert role in ("both", "prefill", "decode"), role
        self.role = role
        # observability (serving.telemetry): the metrics registry is
        # ALWAYS on — the counter attributes below are properties over
        # it, so stats() is a façade over one source of truth — while
        # per-request lifecycle TRACING is opt-in (telemetry=True or an
        # EngineTelemetry instance). Tracing is deliberately NOT a
        # parameter of any program factory: an engine with tracing on
        # launches the identical cached jitted callables (proven by
        # analysis.harness.prove_telemetry_inert), every emission reads
        # host-side scheduler state only, and when disabled each site
        # costs one `is None` check — greedy streams are bitwise
        # identical either way (tests/test_telemetry.py).
        self.metrics = MetricsRegistry()
        if telemetry is True:
            telemetry = EngineTelemetry()
        elif not telemetry:
            # False and None both mean "tracing off" (bench_serving
            # passes the computed bool straight through)
            telemetry = None
        assert telemetry is None or isinstance(telemetry, EngineTelemetry), (
            f"telemetry must be None, a bool, or an EngineTelemetry, "
            f"got {telemetry!r}"
        )
        self.telemetry = telemetry
        # a profiler session is the request log's other switch (step()):
        # whether the log is registered with an open session, and whether
        # it is the session's own and goes when the session does
        self._in_session = False
        self._session_log = False
        # overload degradation knobs: max_queue bounds the wait queue
        # (None = unbounded, the library default); a submit hitting the
        # bound is SHED (AdmissionRejected, the request is dropped for
        # good) or DEFERRED (PoolOverloaded, the caller's backpressure
        # signal to retry later). park_threshold is the eviction-livelock
        # guard: a request evicted that many times in a row without
        # emitting a token parks until pages free up, instead of
        # re-prefilling in a thrash loop.
        assert overload_policy in ("defer", "shed"), overload_policy
        assert max_queue is None or max_queue >= 1, max_queue
        assert park_threshold >= 1, park_threshold
        self.max_queue = max_queue
        self.overload_policy = overload_policy
        self.park_threshold = park_threshold
        # priority admission (replaces FIFO): a queued request's
        # effective priority is ``priority + priority_aging * steps
        # queued`` — aging guarantees starvation-freedom (a request of
        # priority p outranks every FRESH priority-P arrival within
        # ceil((P - p) / priority_aging) scheduler steps of queue
        # residence, ties broken oldest-first; with all-default
        # priorities the order degenerates to exactly the old FIFO).
        # Keyed to SCHEDULER STEPS, not wall clock — the determinism
        # contract the front door's replay tests pin.
        assert priority_aging >= 0.0, priority_aging
        self.priority_aging = priority_aging
        # deterministic fault injection (serving.faults): called at the
        # top of every step() with this engine, AFTER fault_step
        # incremented and BEFORE any dispatch — zero-cost when absent
        # (one is-None check per scheduler window)
        self._fault_hook = fault_hook
        self.fault_step = 0
        # int8 quantized KV pool (serving.paged / quant.py's KV grid):
        # page payloads store int8 with one f32 po2 scale per
        # (page, KV-head) plane, halving the K+V HBM stream every decode
        # step pays — the largest remaining stream after the int8 weight
        # path (PERF.md). Greedy token streams stay invariant across the
        # whole feature matrix (cache x chunking x speculation x
        # eviction x tp): scales are fixed at page birth and every
        # in-dispatch reader sees grid-rounded rows.
        assert kv_quant in (None, "int8"), f"unknown kv_quant {kv_quant!r}"
        self.kv_quant = kv_quant
        # paged-attention backend: "pallas" = the in-kernel block-table
        # walk (ops.paged_attn — no gathered HBM intermediate; compiled,
        # so it needs a TPU unless a test asks for interpret mode),
        # "xla" = the gather path, "auto" = pallas on TPU where
        # ops.paged_attn.supported() says the chip's compiler takes the
        # geometry, xla otherwise (same dispatch philosophy as
        # ops/attention's flash-vs-naive)
        assert paged_kernel in ("auto", "pallas", "xla"), paged_kernel
        # sequence-parallel prefill (ROADMAP item 4): "on" compiles the
        # SP prefill-chunk variant (models.gpt.prefill_chunk_paged
        # sp=True) whose replicated per-token segments shard the chunk's
        # rows over 'tensor' — bitwise the "off" program (the landing
        # gate), with the replicated O(T·D) work and activation traffic
        # scaled 1/tp on long prompts. "auto" = on exactly when the mesh
        # has a tensor axis to shard over; resolved below once tp is
        # known (a tp=1 "on" degenerates to "off": there is no axis, and
        # keeping the resolved value in the program-cache key stops a
        # no-op knob from forking compilations). Decode/verify programs
        # are untouched by construction — separate cache entries, and
        # the 'sp' logical axis is unmapped for them.
        assert prefill_sp in ("auto", "on", "off"), prefill_sp
        # quantized weight path (midgpt_tpu.quant): quant="int8" converts
        # the model to the int8 per-channel serving pytree here, so every
        # program this engine compiles (decode window, prefill chunk,
        # verify) streams int8 weights with the dequant fused into each
        # matmul. Passing an already-quantized model with quant=None is
        # equally valid — the programs accept either form through one
        # code path (GPT.project + the block projections).
        assert quant in (None, "int8"), f"unknown quant mode {quant!r}"
        # what a kind of model has no form for yet is refused here, by
        # name (_REFUSED, ROADMAP Reach)
        kinds = {
            "recurrent": model.config.linear_layers > 0,
            "latent": model.config.latent,
            "block": bool(model.config.block_len),
        }
        asked = {
            "temperature > 0": temperature > 0.0,
            "speculate": bool(speculate),
            "quant": quant is not None,
            "kv_quant": kv_quant is not None,
            "a mesh": mesh is not None,
            "role != 'both'": role != "both",
            "spill": spill == "on",
            "prefill_sp": prefill_sp == "on",
            "block_len": kinds["block"],
        }
        for kind, (subject, refused) in _REFUSED.items():
            bad = [f for f in refused if asked[f]]
            if kinds[kind] and bad:
                raise ValueError(
                    f"{subject} does not support " + ", ".join(bad)
                )
        # layers of two kinds (cfg.layer_types): the recurrent layers' cache
        # (a gated delta rule's or a Mamba selective scan's) is a fixed-size
        # state a slot (serving.paged.RecurrentState), beside the page pool,
        # which then holds the full-attention layers only
        self.hybrid = kinds["recurrent"]
        self._ssm = model.config.recurrent_mixer == "mamba"
        # latent attention (cfg.attention="latent"): the pool holds one
        # payload a page, a token's pooled row, and the decode window runs
        # the paged kernel's latent mode; prefix_cache=True works
        self.latent = model.config.latent
        if quant is not None:
            from midgpt_tpu.quant import is_quantized, quantize_model

            if not is_quantized(model):
                model = quantize_model(model)
        cfg = model.config
        # page grid must tile the context: otherwise a near-block prompt
        # padded up to the page grid exceeds block_size and prefill
        # cannot run (caught in code review)
        assert cfg.block_size % page_size == 0, (
            f"page_size {page_size} must divide block_size {cfg.block_size}"
        )
        assert prefill_chunk is None or prefill_chunk >= 1
        # tensor-parallel serving mesh: shard the model per
        # GPT_PARAM_RULES (column-parallel wqkv/w_up(/gate)/lm_head,
        # row-parallel wo/w_down, quant scales split with their out
        # dim), the KV pool by WHOLE KV HEADS, and the carried logits by
        # vocab. Sequence/pipeline axes have no serving decomposition
        # here (decode is one token deep; DP is shared-nothing engine
        # replicas — serving.cluster — not a sharded slot axis), so a
        # serving mesh is tensor-only (extra replica/fsdp axes are
        # tolerated but simply ride replicated).
        if paged_kernel != "xla":
            from midgpt_tpu.ops.paged_attn import supported as pk_supported
            from midgpt_tpu.utils.platform import is_tpu_backend

            itemsize = 1 if kv_quant == "int8" else jnp.dtype(
                cache_dtype
            ).itemsize
            tp_sz = mesh.shape.get("tensor", 1) if mesh is not None else 1
            geometry = dict(
                pmax=pages_needed(cfg.block_size, page_size),
                page_size=page_size, c=cfg.head_dim, itemsize=itemsize,
                groups=cfg.n_head // cfg.kv_heads,
                # (a block model's forward carries two blocks a slot)
                spec_t=2 * cfg.block_len or speculate + 1,
                heads=max(1, cfg.kv_heads // tp_sz),
            )
            if self.latent:
                # one "KV head" of every query head's rows over the pooled
                # rows as they lie (ops.paged_attn, LATENT MODE)
                geometry.update(
                    c=cfg.latent_row, groups=cfg.n_head, heads=1, latent=True
                )
            kernel_ok = pk_supported(**geometry)
            if paged_kernel == "pallas" and not kernel_ok:
                raise ValueError(
                    "paged_kernel='pallas': the chip's compiler does not "
                    f"take the kernels at {geometry} "
                    "(ops.paged_attn.supported); use 'auto' or 'xla'"
                )
            if paged_kernel == "auto":
                paged_kernel = (
                    "pallas" if is_tpu_backend() and kernel_ok else "xla"
                )
        self.paged_kernel = paged_kernel
        self.tp = 1
        if mesh is not None:
            from midgpt_tpu.models.gpt import (
                GPT_PARAM_RULES,
                mlp_hidden_dim,
            )
            from midgpt_tpu.parallel.sharding import param_shardings

            assert mesh.shape.get("sequence", 1) == 1, (
                "serving meshes cannot shard 'sequence' (decode is one "
                "token deep); use a tensor-only mesh"
            )
            assert mesh.shape.get("pipeline", 1) == 1, (
                "serving meshes cannot shard 'pipeline'; use a "
                "tensor-only mesh"
            )
            tp_sz = mesh.shape.get("tensor", 1)
            assert (
                cfg.n_head % tp_sz == 0 and cfg.kv_heads % tp_sz == 0
            ), (
                f"tensor={tp_sz} must divide heads "
                f"({cfg.n_head}/{cfg.kv_heads}): the pool shards whole "
                "KV heads"
            )
            assert cfg.vocab_size % tp_sz == 0, (
                f"tensor={tp_sz} must divide vocab_size {cfg.vocab_size}"
            )
            assert mlp_hidden_dim(cfg) % tp_sz == 0, (
                f"tensor={tp_sz} must divide the MLP hidden width "
                f"{mlp_hidden_dim(cfg)}"
            )
            self.tp = tp_sz
            model = jax.device_put(
                model, param_shardings(mesh, model, GPT_PARAM_RULES)
            )
        self.prefill_sp = "on" if (
            prefill_sp in ("on", "auto") and self.tp > 1
        ) else "off"
        self.model = model
        self.slots = slots
        self.window = window
        self.page_size = page_size
        self.pad_id = pad_id
        self.clock = clock
        self.block = cfg.block_size
        self.pmax = pages_needed(self.block, page_size)
        if num_pages is None:
            num_pages = slots * self.pmax  # full occupancy, no eviction
        self.alloc = PageAllocator(num_pages)
        self.prefix_cache = prefix_cache
        self.index = PrefixIndex(page_size) if prefix_cache else None
        # cold-page host spill (ROADMAP item 4): under pool pressure,
        # cold (refcount-0 cached) pages move to host RAM — content,
        # int8 scale planes and prefix-index position preserved —
        # instead of being discarded, and fault back through the jitted
        # page-write path (import_pages) on a prefix hit or
        # re-admission. The HBM page id returns to the free list at
        # spill time (that is what frees capacity), so the allocator's
        # id-state identity free+held+cached+quarantined == num_pages is
        # untouched while `spilled` counts host-store entries — the
        # extended ledger the invariant tests check is
        # resident-indexed + spilled == indexed nodes, disjoint
        # (PrefixIndex.check with the store). Spill is a CACHE policy:
        # it needs the prefix index, and a request that cannot fault a
        # spilled node back (pool fully held) degrades to a shorter
        # match, never an error — parking/PoolOverloaded stay the
        # overload surface.
        assert spill in ("on", "off"), spill
        assert spill == "off" or prefix_cache, (
            "spill='on' requires prefix_cache=True: only indexed cold "
            "pages ever spill"
        )
        assert spill_budget_pages is None or spill_budget_pages >= 0
        # prefetch-on-queue (spill="on" only): each scheduler step
        # probes the wait-queue head's prompt against the prefix index
        # and fault-backs its matched SPILLED chain nodes BEFORE
        # admission, in ONE batched import_pages call (bounded per
        # step). "off" degrades to pure fault-on-match at admission —
        # same stream bytes, more import dispatches on the TTFT path.
        assert spill_prefetch in ("on", "off"), spill_prefetch
        self.spill_prefetch = spill_prefetch
        self.spill = spill
        self._spill_store = (
            HostSpillStore(budget_pages=spill_budget_pages)
            if spill == "on" else None
        )
        self.prefill_chunk = prefill_chunk
        # tokens of prefill work allowed between decode windows, handed
        # out in granules of prefill_chunk; the first granule always runs
        # (progress guarantee), so the effective floor is one granule. In
        # front of a K-step decode window the default is one granule a
        # step, so each token of the window waits behind at most one
        # granule's prefill; a verify dispatch is one step, and a block
        # window's `window` counts forwards, so both keep one granule.
        # None (monolithic) -> unlimited
        budget_steps = 1 if speculate or cfg.block_len else window
        self.prefill_budget = (
            prefill_budget
            if prefill_budget is not None or prefill_chunk is None
            else prefill_chunk * budget_steps
        )
        # sampling config: temperature == 0 is greedy, temperature > 0
        # samples — in BOTH the plain window and the speculative verify
        # program (rejection-sampling acceptance; see
        # _build_verify_program). A negative temperature is the only
        # genuinely unsupported sampling config: typed error, not assert
        # (callers surface it as a config problem, not a library bug).
        if temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}"
            )
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be None or >= 1, got {top_k}")
        self.temperature = float(temperature)
        self.top_k = top_k
        assert speculate >= 0, speculate
        if speculate:
            assert speculate < self.block, speculate
        self.speculate = int(speculate)
        self.proposer: tp.Optional[Proposer] = (
            proposer
            if proposer is not None
            else (NgramProposer() if speculate else None)
        )
        # a soft proposer (SoftProposer protocol: soft=True +
        # propose_soft) ships a dense [S, spec_len, V] draft
        # distribution into the verify dispatch; n-gram drafts are
        # one-hot and never materialize it (see serving.speculate)
        self._soft_drafts = bool(
            self.speculate
            and temperature > 0.0
            and getattr(self.proposer, "soft", False)
        )
        # tokens a decode dispatch may write per slot: K for the plain
        # window, spec_len + 1 candidate rows for the verify program —
        # page growth provisions this many
        self._grow = (self.speculate + 1) if self.speculate else window
        # generation by diffusion over blocks (cfg.block_len > 0): a
        # slot's unit of work is a forward of its current block — see
        # _build_block_window
        self.block_len = int(cfg.block_len)
        if self.block_len:
            b = self.block_len
            assert cfg.block_steps >= 1 and b % cfg.block_steps == 0, (
                b, cfg.block_steps
            )
            assert 0 <= cfg.mask_token < cfg.vocab_size, cfg.mask_token
            assert page_size % b == 0, (
                f"a page of {page_size} rows must hold whole blocks of {b}"
            )
            assert prefill_chunk is None or prefill_chunk % b == 0, (
                f"prefill_chunk {prefill_chunk} must be whole blocks of {b}"
            )
            # a block lands with the next one's first forward, and that
            # one takes block_steps forwards, so a window commits at most
            # this many rows a slot
            self._grow = b * -(-window // cfg.block_steps)
        if self.hybrid:
            # prefix_cache=True is accepted and does nothing: a page hit
            # without the recurrent state at that boundary is wrong, so the
            # index is neither consulted nor fed. What it would have served
            # is counted (prefix_hits_refused) from the first pages seen
            self.index = None
            self._first_pages: tp.Set[bytes] = set()
        self.state = (
            RecurrentState.init(cfg, slots, cache_dtype)
            if self.hybrid else None
        )
        # a slot whose request has not prefilled a chunk yet: its next chunk
        # starts the recurrent state from zeros
        self.fresh = np.zeros((slots,), bool)
        self.pool = PagedKVPool.init(
            cfg, num_pages, page_size, cache_dtype, mesh=mesh,
            kv_quant=kv_quant,
        )
        self.logits = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.logits = jax.device_put(
                self.logits, NamedSharding(mesh, P(None, "tensor"))
            )
        self._key = jax.random.PRNGKey(seed)
        self._sentinel = num_pages
        self._mesh = mesh
        # where a decode window's host arrays go: on a mesh, replicated
        # over it as the window's own state outputs are, so that a window
        # taking those outputs (_ahead) runs the program already compiled
        self._window_put = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._window_put = NamedSharding(mesh, P())
        self._max_prefills = (
            max_prefills_per_window
            if max_prefills_per_window is not None
            else slots
        )

        # host-side slot state
        self.bt = np.full((slots, self.pmax), self._sentinel, np.int32)
        self.pooled_len = np.zeros((slots,), np.int32)
        self.done = np.ones((slots,), bool)  # empty slots ride as done
        self.prefilling = np.zeros((slots,), bool)
        # prefill-role engines: slot fully prefilled, parked awaiting the
        # cluster's page export (done stays True — no decode window ever
        # carries a handoff-ready slot)
        self.handoff_ready = np.zeros((slots,), bool)
        # scripted `handoff` fault (serving.faults): armed by the hook,
        # fires inside the next export_request
        self._handoff_poison = False
        self.emitted = np.zeros((slots,), np.int32)
        self.budget = np.zeros((slots,), np.int32)
        self.eos = np.full((slots,), -1, np.int32)
        self.seeds = np.zeros((slots,), np.int32)
        self.slot_pages: tp.List[tp.List[int]] = [[] for _ in range(slots)]
        self.slot_req: tp.List[tp.Optional[Request]] = [None] * slots
        # the slot's context tokens (prompt + generated) — what its page
        # contents encode; drives content registration in the index
        self.slot_ctx: tp.List[tp.List[int]] = [[] for _ in range(slots)]
        # pages of the slot already walked for registration (matched
        # pages count: they were indexed before admission)
        self.slot_registered: tp.List[int] = [0] * slots
        # the index node (page id, -1 = root) the slot's chain is at
        self.slot_node: tp.List[int] = [PrefixIndex._ROOT] * slots
        # extra refcounts the slot holds on CANONICAL pages it chains
        # through without owning (register() returned someone else's
        # identical-content page): pinned so LRU reclaim can never leave
        # slot_node/parent ids dangling in the index
        self.slot_pins: tp.List[tp.List[int]] = [[] for _ in range(slots)]
        # block-diffusion: each slot's current block (tokens, revealed
        # set, reveal step per position), the complete block before it
        # while its K/V are yet to land (_build_block_window) and, per
        # layer and expert, the rows the window forwards routed there
        if self.block_len:
            blk = (slots, self.block_len)
            self.blk_tok = np.full(blk, cfg.mask_token, np.int32)
            self.blk_rev = np.zeros(blk, bool)
            self.blk_at = np.full(blk, -1, np.int32)
            self.blk_pend = np.zeros(slots, bool)
            self.blk_pend_tok = np.full(blk, cfg.mask_token, np.int32)
            self.expert_rows = np.zeros(
                (cfg.n_layer, max(1, cfg.experts)), np.int64
            )
        # round-robin cursor over prefilling slots (persists across
        # windows so a one-chunk budget still alternates slots)
        self._prefill_rr = 0
        # the decode window enqueued and not yet read (_run_window): one
        # that a step dispatched behind the window it then read waits here
        # for the next step's harvest
        self._flight: tp.Optional[_Flight] = None

        self.queue: tp.Deque[Request] = collections.deque()
        # overload parking lot: requests evicted by the livelock guard or
        # by single-slot pool exhaustion wait here (progress kept) until
        # a finish / quarantine release / idle engine un-parks them
        self.parked: tp.List[Request] = []
        self.finished: tp.Dict[int, Request] = {}
        # post-admission terminal outcomes that are NOT completions:
        # cancelled (submitter teardown) and expired (deadline shed
        # before dispatch) — separate dicts so goodput accounting and
        # the finished-equals-submitted test contracts stay exact
        self.cancelled: tp.Dict[int, Request] = {}
        self.expired: tp.Dict[int, Request] = {}
        self._next_rid = 0
        self._queue_seq = 0  # fresh-submission order (priority tie-break)
        # rid -> live Request (queued, parked, or in a slot): the O(1)
        # side of lookup() — the front door's per-round harvest reads
        # every live stream's progress through it, and a linear scan of
        # queue+parked+slots per stream would make each round O(n^2)
        # under a deep backlog
        self._live: tp.Dict[int, Request] = {}

        if self.block_len:
            self._verify_fn = None
            self._window_fn = make_block_window(
                model, slots=slots, window=window, pmax=self.pmax,
                rope_len=self.block, mesh=mesh,
                paged_kernel=self.paged_kernel,
            )
        elif self.speculate:
            # speculation REPLACES the K-step window: every decode
            # dispatch is a verify dispatch (1 + accepted tokens/slot)
            self._verify_fn = make_verify_program(
                model,
                slots=slots,
                spec_len=self.speculate,
                pmax=self.pmax,
                rope_len=self.block,
                pad_id=pad_id,
                temperature=temperature,
                top_k=top_k,
                soft_drafts=self._soft_drafts,
                mesh=mesh,
                paged_kernel=self.paged_kernel,
            )
            self._window_fn = None
        else:
            self._verify_fn = None
            self._window_fn = make_decode_window(
                model,
                slots=slots,
                window=window,
                pmax=self.pmax,
                rope_len=self.block,
                pad_id=pad_id,
                temperature=temperature,
                top_k=top_k,
                mesh=mesh,
                paged_kernel=self.paged_kernel,
            )
        self._chunk_fns: tp.Dict[int, tp.Any] = {}
        self._copy_fn = make_copy_page_program()

        # counters (bench_serving / tests): each name in
        # _ENGINE_COUNTERS is a class-level property over self.metrics
        # (serving.telemetry.MetricsRegistry), so `+= 1` here, the
        # bench's warmup `setattr(e, name, 0)` reset, and the metrics
        # snapshot all hit the SAME Counter objects — stats() keeps its
        # exact key inventory (telemetry.ENGINE_STATS_KEYS, pinned by
        # test) as a façade over the registry
        for _n in _ENGINE_COUNTERS:
            self.metrics.counter(_n)
        self.reject_reasons: tp.Dict[str, int] = {}
        self.metrics.attach_labels("reject_reasons", self.reject_reasons)
        # live-state gauges, evaluated lazily at snapshot time (no
        # mirrored writes on the scheduler hot path)
        g = self.metrics.gauge
        g("free_pages", lambda: self.alloc.free_pages)
        g("cached_pages", lambda: self.alloc.cached_pages)
        g("spill_resident_pages",
          lambda: len(self._spill_store)
          if self._spill_store is not None else 0)
        g("spill_resident_bytes",
          lambda: self._spill_store.nbytes
          if self._spill_store is not None else 0)
        g("pool_utilization",
          lambda: 1.0 - self.alloc.free_pages / max(1, self.alloc.num_pages))
        g("queue_depth", lambda: len(self.queue))
        g("parked_requests", lambda: len(self.parked))
        g("active_slots", lambda: len(self._active_slots()))
        g("slot_occupancy",
          lambda: self.occupancy_sum / max(1, self.windows * self.slots))
        g("prefix_hit_rate",
          lambda: self.prompt_tokens_cached
          / max(1, self.prompt_tokens_total))
        g("spec_acceptance_rate",
          lambda: self.spec_accepted / max(1, self.spec_drafted))
        g("tokens_per_dispatch",
          lambda: self.tokens_generated / max(1, self.decode_dispatches))
        # fixed-bucket latency histograms: queue_delay/ttft/e2e observe
        # from the scheduler's own clock reads (always on — no device
        # access); tbt/dispatch need token timestamps, so they populate
        # only under tracing
        for _h in ("queue_delay_s", "ttft_s", "e2e_s", "tbt_s",
                   "dispatch_s"):
            self.metrics.histogram(_h)

    # -- submission ---------------------------------------------------------

    def _reject(self, reason: str, message: str) -> tp.NoReturn:
        """Typed, counted admission rejection (machine-readable reason)."""
        self.admission_rejected += 1
        self.reject_reasons[reason] = self.reject_reasons.get(reason, 0) + 1
        raise AdmissionRejected(reason, message)

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        eos_id: tp.Optional[int] = None,
        seed: int = 0,
        priority: int = 0,
        deadline_s: tp.Optional[float] = None,
        deadline: tp.Optional[float] = None,
    ) -> int:
        """Queue a request; returns its id. Prompts are cropped to the last
        ``block_size - max_new_tokens`` tokens so the whole context fits.

        ``priority`` (higher dispatches first; aged by
        ``priority_aging`` per queued scheduler step so low priorities
        provably cannot starve) and a deadline (``deadline_s`` relative
        to now on this engine's clock, or ``deadline`` as an absolute
        clock instant — the cluster's cold-failover record uses the
        absolute form so a re-served request keeps its ORIGINAL SLO)
        feed the admission policy: a request whose deadline passes
        while still queued/parked is shed before dispatch
        (``Request.outcome == "expired"``, the ``deadline_shed`` event,
        the ``deadline_shed_requests`` counter — serving.faults
        ``DeadlineExceeded`` is the exception form the front door
        raises).

        Unservable requests raise :class:`AdmissionRejected` (permanent:
        a bad budget, an empty prompt, or a lifetime page demand larger
        than the whole pool — nothing the engine does later can serve
        it); a full bounded wait queue raises AdmissionRejected under
        ``overload_policy="shed"`` or :class:`PoolOverloaded` under
        ``"defer"`` (transient — the caller's cue to back off and
        resubmit; the front door turns it into awaitable backpressure).
        Both are counted in :meth:`stats` — overload must show up in
        telemetry, not as a crash."""
        with span("midgpt.engine.submit"):
            if max_new_tokens < 1:
                self._reject(
                    "bad_budget", f"max_new_tokens {max_new_tokens} < 1"
                )
            if max_new_tokens >= self.block:
                self._reject(
                    "budget_exceeds_block",
                    f"max_new_tokens {max_new_tokens} must leave room for at "
                    f"least one prompt token in block_size {self.block}",
                )
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.size < 1:
                self._reject("empty_prompt", "prompt has no tokens")
            keep = self.block - max_new_tokens
            if prompt.size > keep:
                prompt = prompt[-keep:]
            lifetime = pages_needed(
                self._lifetime_tokens(int(prompt.size), max_new_tokens),
                self.page_size,
            )
            if lifetime > self.alloc.num_pages:
                self._reject(
                    "lifetime_exceeds_pool",
                    f"request needs {lifetime} pages over its lifetime but "
                    f"the pool holds {self.alloc.num_pages}; raise num_pages",
                )
            if (
                self.max_queue is not None
                and len(self.queue) >= self.max_queue
            ):
                if self.overload_policy == "shed":
                    self.shed_requests += 1
                    self._emit("shed", reason="queue_full")
                    self._reject(
                        "queue_full",
                        f"wait queue at max_queue={self.max_queue}; shed",
                    )
                self.deferred_submits += 1
                self._emit("deferred", reason="queue_full")
                raise PoolOverloaded(
                    "queue_full",
                    f"wait queue at max_queue={self.max_queue}; retry later",
                )
            if deadline is None and deadline_s is not None:
                deadline = self.clock() + deadline_s
            req = self.make_request(
                prompt, max_new_tokens, eos_id=eos_id, seed=seed,
                priority=priority, deadline=deadline,
            )
            # the rid resubmit is about to assign — emitted here so the
            # lifecycle reads submit -> queued in order. Scheduling fields
            # ride in the event data only when non-default, so existing
            # replay signatures are untouched (priority is a deterministic
            # caller input; the absolute deadline is a clock value and
            # stays out — has_deadline is the deterministic projection).
            extra: tp.Dict[str, tp.Any] = {}
            if priority:
                extra["priority"] = int(priority)
            if deadline is not None:
                extra["has_deadline"] = True
            self._emit(
                "submit", rid=self._next_rid, t=req.submit_time,
                prompt_tokens=int(req.prompt.size), budget=int(max_new_tokens),
                **extra,
            )
            return self.resubmit(req)

    def _lifetime_tokens(self, prompt: int, new: int) -> int:
        """Rows a request's K/V can come to: its tokens, in whole blocks
        for a block-diffusion model (generation runs to the end of the
        block that holds the last token asked for)."""
        b = self.block_len or 1
        return -(-(prompt + new) // b) * b

    def make_request(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        eos_id: tp.Optional[int] = None,
        seed: int = 0,
        priority: int = 0,
        deadline: tp.Optional[float] = None,
    ) -> Request:
        """Build a :class:`Request` exactly as :meth:`submit` would —
        crop included — WITHOUT admission control or queueing. The
        cluster's cold-failover path uses this + :meth:`resubmit` to
        re-serve an already-accepted request from scratch (``deadline``
        is absolute, so the re-served request keeps its original
        SLO)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        keep = self.block - max_new_tokens
        if prompt.size > keep:
            prompt = prompt[-keep:]
        return Request(
            rid=-1,  # assigned at resubmit
            prompt=prompt,
            prompt0=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=-1 if eos_id is None else int(eos_id),
            seed=seed,
            submit_time=self.clock(),
            spec_k=self.speculate,
            priority=int(priority),
            deadline=deadline,
        )

    def resubmit(self, req: Request) -> int:
        """Failover re-admission (serving.cluster): enqueue an already-
        accepted :class:`Request` — typically drained off a dead replica
        — under a fresh engine-local id, progress preserved. Bypasses
        the bounded-queue admission control on purpose: this is work the
        cluster already accepted, not new load."""
        rid = self._next_rid
        self._next_rid += 1
        req.rid = rid
        req.queue_seq = self._queue_seq
        self._queue_seq += 1
        req.queue_step = self.fault_step  # aging baseline
        self._live[rid] = req
        self.queue.append(req)
        self._emit(
            "queued", rid=rid, prompt_tokens=int(req.prompt.size),
            tokens_emitted=len(req.tokens),
        )
        return rid

    def drain_requests(self) -> tp.List[Request]:
        """Hand every live request off this engine (failover): in-flight
        slots are converted exactly like an eviction (context rebuilt
        from the original prompt + all emitted tokens, budget intact),
        then the wait queue and the parking lot follow. The engine is
        left empty; its pages return to the allocator. Because requests
        carry only really-emitted tokens — faults fire at step
        boundaries, before any dispatch mutates state — a survivor
        resuming a drained request continues the stream bit-identically
        (the eviction/re-admission contract, plus placement invariance
        across replicas). A window in flight is read first: its tokens are
        the ones the stream would carry next, and a request it finished
        leaves as finished."""
        self._settle()
        out: tp.List[Request] = []
        for s in self._active_slots():
            req = self.slot_req[s]
            req.prompt = np.concatenate(
                [req.prompt0, np.asarray(req.tokens, np.int32)]
            )
            self._emit("evicted", rid=req.rid, slot=s, drained=True)
            self._release_slot(s)
            out.append(req)
        out.extend(self.queue)
        self.queue.clear()
        out.extend(self.parked)
        self.parked.clear()
        self._live.clear()  # every live request just left this engine
        return out

    # -- page handoff (the disaggregated cluster's seam) --------------------

    def handoff_ready_slots(self) -> tp.List[int]:
        """Slots whose prompt is fully prefilled and parked for export
        (prefill-role engines only; always empty elsewhere)."""
        return [s for s in range(self.slots) if self.handoff_ready[s]]

    def export_request(self, s: int) -> HandoffRecord:
        """Package handoff-ready slot ``s`` for a decode-class engine:
        page payloads (+ int8 scale planes) and the carried logits row
        leave as host arrays, then the slot releases through the normal
        path — indexed pages retire COLD, so this prefill replica's
        prefix cache keeps serving hits on the exported chain (that is
        what makes prefix-affinity routing to prefill replicas pay).

        Raises :class:`HandoffFailed` when a scripted ``handoff`` fault
        is armed — BEFORE any state leaves the slot, so the request is
        still intact here and the cluster can abandon this copy and
        re-serve cold from its submission record (streams bit-identical
        by the determinism contract)."""
        self._settle()
        if self.hybrid:
            raise ValueError(
                "a model with recurrent layers does not support "
                "export_request: the pages would leave without the "
                "recurrent state"
            )
        req = self.slot_req[s]
        assert req is not None and bool(self.handoff_ready[s]), (s, req)
        if self._handoff_poison:
            self._handoff_poison = False
            raise HandoffFailed(
                f"scripted handoff fault exporting rid {req.rid} "
                f"(slot {s})"
            )
        p = int(self.pooled_len[s])
        n_pages = pages_needed(p, self.page_size)
        ids = [int(x) for x in self.bt[s, :n_pages]]
        k, v, sk, sv = export_pages(self.pool, ids)
        rec = HandoffRecord(
            req=req,
            ctx=list(self.slot_ctx[s]),
            resident=p,
            # the final prefill chunk wrote exactly the logits a
            # monolithic prefill would leave; carrying this row is what
            # makes the first decoded token bit-identical
            logits_row=np.asarray(self.logits[s], np.float32),
            n_pages=n_pages,
            k=k, v=v, sk=sk, sv=sv,
        )
        self._emit(
            "handoff", rid=req.rid, slot=s, direction="export",
            pages=n_pages,
        )
        self._live.pop(req.rid, None)
        self._release_slot(s)
        return rec

    def import_request(self, rec: HandoffRecord) -> tp.Optional[int]:
        """Land a :class:`HandoffRecord` in a free slot of THIS engine:
        alias whatever full-page prefix this pool's index already holds
        (same match-pin discipline as admission — capped at the last
        prompt token, so the append page is always private), import the
        remaining pages' payloads byte-exactly, point the block table at
        them, set the carried logits row, and re-register the chain in
        this pool's prefix index so the handed-off prefix serves future
        hits here too. Returns the fresh engine-local rid, or None when
        no slot or no page capacity is available right now (the cluster
        keeps the record and retries next step).

        The slot resumes decoding exactly where a local prefill would
        have left it (same pooled_len, same logits row, same request
        seed), so the stream is bit-identical to the monolithic engine —
        and a later eviction under pressure re-prefills locally through
        the ordinary (also bit-identical) eviction path."""
        self._settle()
        if self.hybrid:
            raise ValueError(
                "a model with recurrent layers does not support "
                "import_request: a record carries no recurrent state"
            )
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        if not free:
            return None
        s = free[0]
        ctx = rec.ctx
        p = rec.resident
        assert p == len(ctx) and rec.n_pages == pages_needed(
            p, self.page_size
        ), (p, len(ctx), rec.n_pages)
        req = rec.req
        full: tp.List[int] = []
        if self.index is not None:
            full, _, _ = self.index.match(ctx[: p - 1])
            # a match can walk onto HOST-SPILLED nodes (virtual ids, a
            # suffix of the chain) — no fault-back here: the record
            # already carries those pages' bytes, so truncate and
            # import them; _register_pages re-adopts the spilled nodes
            # through the ordinary re-admission path (payload dropped,
            # no import dispatch wasted)
            for i, pg in enumerate(full):
                if self.index.is_spilled(pg):
                    full = full[:i]
                    break
        for pg in full:
            self.alloc.incref(pg)
            self.index.revive(pg)
        need = rec.n_pages - len(full)
        if not self._try_reserve(need):
            self._release_pages(full)
            return None
        fresh = self.alloc.alloc(need)
        pages = full + fresh
        # payload lands only on the non-aliased pages (the exported
        # stack is block-table-ordered, so the aliased prefix occupies
        # positions 0..len(full)-1 and already holds identical bytes by
        # the content-chain contract)
        self.pool = import_pages(
            self.pool, fresh,
            rec.k[:, len(full):], rec.v[:, len(full):],
            None if rec.sk is None else rec.sk[:, len(full):],
            None if rec.sv is None else rec.sv[:, len(full):],
        )
        rid = self._next_rid
        self._next_rid += 1
        req.rid = rid
        self._live[rid] = req
        self.slot_req[s] = req
        self.slot_pages[s] = list(pages)
        self.slot_pins[s] = []
        self.bt[s, :] = self._sentinel
        self.bt[s, : rec.n_pages] = pages
        self.pooled_len[s] = p
        self.done[s] = False
        self.prefilling[s] = False
        self.handoff_ready[s] = False
        self.emitted[s] = len(req.tokens)
        self.budget[s] = req.max_new_tokens
        self.eos[s] = req.eos_id
        self.seeds[s] = req.seed
        self.slot_ctx[s] = [int(t) for t in ctx]
        self.slot_registered[s] = len(full)
        self.slot_node[s] = full[-1] if full else PrefixIndex._ROOT
        self.logits = self.logits.at[s].set(
            jnp.asarray(rec.logits_row, jnp.float32)
        )
        self._register_pages(s)
        self._emit(
            "handoff", rid=rid, slot=s, direction="import",
            pages=rec.n_pages, aliased=len(full), imported=need,
        )
        return rid

    # -- cancellation + lookup (the front door's seams) ---------------------

    def cancel(self, rid: int) -> bool:
        """Tear a live request down: queued/parked entries leave their
        waiting structure, an in-flight slot is reclaimed IMMEDIATELY
        (this is a host-side scheduler mutation — the next window simply
        no longer carries the slot) and its pages release through the
        same path a finish takes, so indexed pages retire COLD and
        future prefix hits survive the cancellation. Mid-speculation
        the per-slot write watermark already guarantees no stale draft
        K/V ever landed in the pages, and COW refcounts unwind through
        ``_release_slot``'s pins — the allocator/index invariants hold
        after every cancel (property-tested by the front-door suite). A
        window in flight is read first, so the request leaves with its
        tokens, or as finished where that window ended it.

        Returns True when ``rid`` was live; False for unknown or
        already-terminal ids (idempotent — a double cancel is a no-op).
        The outcome is recorded (``Request.outcome = "cancelled"``, the
        ``cancelled`` event, the ``cancelled_requests`` counter), never
        raised — :class:`~midgpt_tpu.serving.faults.Cancelled` is the
        front door's exception form."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._cancelled(req, where="queued")
                return True
        for i, req in enumerate(self.parked):
            if req.rid == rid:
                self.parked.pop(i)
                self._cancelled(req, where="parked")
                return True
        self._settle()
        for s in self._active_slots():
            req = self.slot_req[s]
            if req.rid == rid:
                self._cancelled(req, where="slot", slot=s)
                self._release_slot(s)
                if self.parked:
                    self._unpark()  # freed pages: parked work retries
                return True
        return False

    def _cancelled(self, req: Request, **data) -> None:
        req.outcome = "cancelled"
        self.cancelled_requests += 1
        self._live.pop(req.rid, None)
        self.cancelled[req.rid] = req
        self._emit(
            "cancelled", rid=req.rid, tokens_emitted=len(req.tokens),
            **data,
        )

    def _expire(self, req: Request, **data) -> None:
        """Deadline shed: the request's deadline passed while it was
        still waiting for dispatch (queued, or parked at release
        time) — drop it before spending compute it can no longer bank
        under the SLO."""
        req.outcome = "expired"
        self.deadline_shed_requests += 1
        self._live.pop(req.rid, None)
        self.expired[req.rid] = req
        self._emit(
            "deadline_shed", rid=req.rid, tokens_emitted=len(req.tokens),
            **data,
        )

    def lookup(self, rid: int) -> tp.Optional[Request]:
        """The :class:`Request` for an engine-local id, wherever its
        lifecycle has it (queued, parked, in a slot, or terminal);
        None for an unknown id. O(1) — the front door's harvest reads
        every live stream's token progress through this each round.
        The object is stable across evictions/parks within one engine,
        so a cursor over ``req.tokens`` streams exactly the emitted
        tokens."""
        return (
            self._live.get(rid)
            or self.finished.get(rid)
            or self.cancelled.get(rid)
            or self.expired.get(rid)
        )

    # -- internals ----------------------------------------------------------

    def _emit(self, kind: str, rid=None, t=None, **data) -> None:
        """One lifecycle event into the attached telemetry — a no-op
        `is None` check when tracing is off (the clock is not even
        read). Data fields must be deterministic under replay; wall
        clock rides only in ``t`` (serving.telemetry.Event)."""
        tele = self.telemetry
        if tele is None:
            return
        tele.emit(
            kind, step=self.fault_step,
            t=self.clock() if t is None else t, rid=rid, **data,
        )

    def _active_slots(self) -> tp.List[int]:
        return [s for s in range(self.slots) if self.slot_req[s] is not None]

    def _decoding_slots(self) -> tp.List[int]:
        return [
            s
            for s in range(self.slots)
            if self.slot_req[s] is not None
            and not self.prefilling[s]
            and not self.handoff_ready[s]
        ]

    def _prefill_bucket(self, p: int) -> int:
        """Padded chunk length: pages rounded up to a power of two, so the
        number of compiled prefill programs is O(log(block/page_size)).
        Under a granule below ``PREFILL_MERGE_ROWS`` a program is never
        shorter than the granule: there its time is its weight stream, so
        the padding costs next to nothing, and an engine that merges
        granules builds the programs from the granule up to the cap (on a
        64-token granule 64, 128 and 256 rows) and none below."""
        if self.prefill_chunk is not None and (
            self.prefill_chunk < PREFILL_MERGE_ROWS
        ):
            p = max(p, self.prefill_chunk)
        n = pages_needed(p, self.page_size)
        n = 1 << (n - 1).bit_length()
        return min(n * self.page_size, self.pmax * self.page_size)

    # -- page accounting with cold-cache spill ------------------------------

    def _try_reserve(
        self, n: int, protect: tp.Optional[tp.AbstractSet[int]] = None
    ) -> bool:
        """Make ``n`` pages allocatable, reclaiming cold cached prefixes
        LRU-leaf-first under pressure; False when the pool genuinely
        cannot produce them. refcount>0 pages are never touched, which is
        why callers PIN (incref) any matched chain before reserving —
        attempt-based rather than counting-based, because a cold page is
        only reclaimable once no held page chains through it.

        With ``spill="on"`` the same LRU-leaf-first order SPILLS instead
        of discarding: the victim's payload (all layers + int8 scale
        planes) exports to the host store, the index re-keys the node
        virtual (still matchable), and only then does the HBM id return
        to the free list. Past ``spill_budget_pages`` the oldest spilled
        prefixes are forgotten outright — bounded host residency, with
        plain reclaim as the degradation floor. ``protect`` names
        spilled vids an in-flight fault-back still needs: budget
        enforcement skips them (host residency may transiently overshoot
        until the fault-back pops them itself) rather than dropping a
        chain node mid-materialization, which would strand a virtual id
        in the slot's block table."""
        while not self.alloc.can_alloc(n):
            if self.index is None:
                return False
            if self._spill_store is not None:
                victim = self.index.coldest_leaf()
                if victim is None:
                    return False
                payload = export_pages(self.pool, [victim])
                vid = self.index.spill(victim)
                self._spill_store.put(vid, payload)
                self.alloc.reclaim(victim)
                self.spilled_pages += 1
                while self._spill_store.over_budget:
                    dropped = self.index.discard_spilled_oldest(protect)
                    if dropped is None:
                        # every discardable node is protected: carry the
                        # overshoot; the fault-back pops them shortly
                        assert protect, "over budget with nothing spilled"
                        break
                    self._spill_store.pop(dropped)
                    self.spill_discards += 1
            else:
                victim = self.index.evict_cold_leaf()
                if victim is None:
                    return False
                self.alloc.reclaim(victim)
                self.cold_reclaims += 1
        return True

    def _fault_back(
        self,
        vid: int,
        protect: tp.Optional[tp.AbstractSet[int]] = None,
    ) -> tp.Optional[int]:
        """Restore one spilled node to a freshly allocated resident page
        through the jitted page-write path (import_pages — byte-exact,
        so the revived prefix reads back bit-identically). Returns the
        new page id at refcount 1 (the caller's pin), or None when the
        pool cannot produce a page even by spilling others — the caller
        degrades to a shorter prefix match instead of wedging.
        ``protect`` (which must cover ``vid`` and every other spilled
        node of the chain being materialized) keeps the reservation's
        own budget-discard pass from dropping the payloads this
        fault-back is about to import."""
        assert self._spill_store is not None and self.index is not None
        if not self._try_reserve(1, protect=protect):
            return None
        [page] = self.alloc.alloc(1)
        k, v, sk, sv = self._spill_store.pop(vid)
        self.pool = import_pages(self.pool, [page], k, v, sk, sv)
        self.index.unspill(vid, page)
        self.spill_faultback_pages += 1
        return page

    def _fault_back_matched(
        self,
        full: tp.List[int],
        cow_src: tp.Optional[int],
        matched: int,
        protect: tp.Optional[tp.AbstractSet[int]] = None,
    ) -> tp.Tuple[tp.List[int], tp.Optional[int], int, tp.Set[int]]:
        """Materialize any spilled nodes a prefix match walked onto.
        Spilled subtrees are closed downward, so the spilled nodes of a
        matched chain form a SUFFIX of ``full`` (plus possibly the COW
        source, a child of the tail): fault them back in chain order —
        each parent must be resident before its child re-keys under it.
        ``protect`` must hold the chain's spilled vids so no fault-back's
        reservation can budget-discard a later node of the same chain.
        Returns the match with virtual ids replaced by resident page
        ids, plus the set of pages already holding their pin (alloc at
        refcount 1 — the pin loop must not incref them again). A failed
        fault-back truncates the match at that node — the dropped
        tokens recompute, the stream is unchanged."""
        prepinned: tp.Set[int] = set()
        if self._spill_store is None:
            return full, cow_src, matched, prepinned
        for i, node in enumerate(full):
            if not self.index.is_spilled(node):
                continue
            page = self._fault_back(node, protect=protect)
            if page is None:
                # drop the spilled suffix (and the COW source — it
                # chains under the tail); those tokens just recompute
                full = full[:i]
                return full, None, len(full) * self.page_size, prepinned
            full[i] = page
            prepinned.add(page)
        if cow_src is not None and self.index.is_spilled(cow_src):
            page = self._fault_back(cow_src, protect=protect)
            if page is None:
                return full, None, len(full) * self.page_size, prepinned
            cow_src = page
            prepinned.add(page)
        return full, cow_src, matched, prepinned

    # pages faulted back per scheduler step by prefetch-on-queue; one
    # batched import_pages dispatch covers the whole bound, so raising
    # it trades step-time import bytes against extra queue-wait steps
    _SPILL_PREFETCH_BOUND = 8

    def _spill_prefetch(self) -> None:
        """Prefetch-on-queue: probe the wait-queue HEAD's prompt against
        the prefix index and fault back the matched chain's spilled
        nodes BEFORE admission — one batched :func:`import_pages` call
        per step (bounded), instead of one import dispatch per node at
        admit time. Prefetched pages park cold-resident at refcount 0,
        so the admission that follows pins them through the ordinary
        resident-chain path; byte-exact imports keep the stream bitwise
        identical to fault-on-match, only the dispatch count on the
        TTFT path drops.

        Discipline mirrors :meth:`_admit`: the chain's RESIDENT nodes
        are pinned first so the reservation can never spill a parent out
        from under a child about to unspill, and the chain's spilled
        vids ride the reservation's protect-set (the PR 19 fix) so the
        budget-discard pass cannot drop the payloads being prefetched.
        A failed reservation degrades to fault-on-match at admission —
        never an error."""
        if (
            self._spill_store is None
            or self.spill_prefetch != "on"
            or not self.queue
            or self.index is None
            or not len(self._spill_store)
        ):
            return
        req = self.queue[self._select_queued()]
        p = int(req.prompt.size)
        full, cow_src, _ = self.index.match(req.prompt[: p - 1])
        cand = list(full) + ([cow_src] if cow_src is not None else [])
        spilled = [pg for pg in cand if self.index.is_spilled(pg)]
        if not spilled:
            return
        # chain order: full's spilled nodes are a suffix of the chain,
        # the COW source chains under its tail — truncating to a PREFIX
        # of that list keeps every parent ahead of its child
        vids = spilled[: self._SPILL_PREFETCH_BOUND]
        pinned = [pg for pg in cand if pg not in set(spilled)]
        for pg in pinned:
            self.alloc.incref(pg)
            self.index.revive(pg)
        if not self._try_reserve(len(vids), protect=set(vids)):
            self._release_pages(pinned)
            return
        pages = self.alloc.alloc(len(vids))
        payloads = [self._spill_store.pop(v) for v in vids]
        k = np.concatenate([pl[0] for pl in payloads], axis=1)
        v = np.concatenate([pl[1] for pl in payloads], axis=1)
        sk = sv = None
        if payloads[0][2] is not None:
            sk = np.concatenate([pl[2] for pl in payloads], axis=1)
            sv = np.concatenate([pl[3] for pl in payloads], axis=1)
        self.pool = import_pages(self.pool, pages, k, v, sk, sv)
        for vid, page in zip(vids, pages):
            self.index.unspill(vid, page)
        self.spill_faultback_pages += len(pages)
        self.spill_prefetch_pages += len(pages)
        # decref to 0 → cold-resident and matchable: admission pins them
        self._release_pages(pinned + list(pages))

    def _release_pages(self, pages: tp.Iterable[int]) -> None:
        """Decref a request's pages: indexed ones retire to the cold
        prefix cache (still matchable), private ones free outright."""
        for p in pages:
            cached = self.index is not None and p in self.index
            if self.alloc.decref(p, cache=cached) == 0 and cached:
                self.index.touch_cold(p)

    # -- admission ----------------------------------------------------------

    def _shed_expired_queued(self) -> None:
        """Drop every queued request whose deadline already passed —
        BEFORE dispatch, so no window is spent on tokens the SLO can no
        longer bank. Zero-cost without deadlines: the clock is read
        only when a deadline-carrying request is actually queued."""
        now: tp.Optional[float] = None
        for req in [r for r in self.queue if r.deadline is not None]:
            if now is None:
                now = self.clock()
            if now > req.deadline:
                self.queue.remove(req)
                self._expire(req, where="queued")

    def _select_queued(self) -> int:
        """Index of the next request to admit. Two bands:

        1. RESUMED work (``evictions > 0`` — eviction/park re-queues
           with progress kept) goes first, in queue order: it holds an
           in-flight budget promise and re-prefills mostly from cache,
           and this reproduces the old appendleft-FIFO discipline
           exactly.
        2. Fresh submissions by aged effective priority
           ``priority + priority_aging * (steps queued)``, FIFO
           (``queue_seq``) within a band — so equal priorities ARE the
           old FIFO, and a starved low priority provably ages past any
           fixed higher priority (the front-door starvation test pins
           the bound).

        Deterministic: every key component is a scheduler-step or
        submission-order quantity, never wall clock."""
        best, best_key = 0, None
        for i, req in enumerate(self.queue):
            if req.evictions > 0:
                key: tp.Tuple = (0, i)
            else:
                eff = req.priority + self.priority_aging * (
                    self.fault_step - req.queue_step
                )
                key = (1, -eff, req.queue_seq)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _admit(self) -> None:
        self._shed_expired_queued()
        admitted = 0
        for s in range(self.slots):
            if not self.queue or admitted >= self._max_prefills:
                break
            if self.slot_req[s] is not None:
                continue
            qi = self._select_queued()
            req = self.queue[qi]
            p = int(req.prompt.size)
            # prefix-cache match, capped at p-1: the last prompt token is
            # ALWAYS recomputed — its forward pass is what produces the
            # first decode logits (and, page-granularly, guarantees the
            # slot's append page is never a shared one)
            full: tp.List[int] = []
            cow_src: tp.Optional[int] = None
            matched = 0
            target = self._prefill_target(p)
            if self.index is not None:
                # (a block-diffusion prompt may be resident whole: its
                # first block opens from the mask token, not from logits)
                full, cow_src, matched = self.index.match(
                    req.prompt[: target if self.block_len else p - 1]
                )
                if self.block_len and cow_src is not None:
                    # a block's K/V are those of its final tokens, ALL of
                    # them: a page matched in part may end inside a block,
                    # so only whole pages (whole blocks) are reused
                    cow_src, matched = None, len(full) * self.page_size
            # PIN the matched chain (and the COW source, until its copy
            # lands) before reserving: revived out of the LRU, the
            # reservation below can never reclaim (or spill) them out
            # from under us. Spilled nodes — virtual ids forming a
            # suffix of the chain (spilled subtrees are closed
            # downward), possibly plus the COW source — cannot be
            # increfed: they fault back AFTER the resident pins land,
            # each returning a fresh page already carrying its pin at
            # refcount 1.
            cand = list(full) + ([cow_src] if cow_src is not None else [])
            spilled_vids = (
                {pg for pg in cand if self.index.is_spilled(pg)}
                if self.index is not None else set()
            )
            pinned = [pg for pg in cand if pg not in spilled_vids]
            for pg in pinned:
                self.alloc.incref(pg)
                self.index.revive(pg)
            # Reserve the WHOLE demand — fresh pages plus one per
            # spilled chain node — BEFORE any fault-back import: a
            # head-of-line block must cost zero import_pages dispatches
            # (pages imported first would unpin straight back to cold
            # and re-spill on every retry of a blocked large request).
            # The chain's spilled vids are protected so the
            # reservation's own budget-discard pass cannot drop the
            # payloads about to be materialized.
            need = pages_needed(p, self.page_size) - len(full)
            if not self._try_reserve(
                need + len(spilled_vids), protect=spilled_vids
            ):
                # head-of-line blocks: unpin and wait for pages to free
                # (deliberately no skip-ahead to a smaller request —
                # bypassing the selected head would starve large ones)
                self._release_pages(pinned)
                break
            if self._spill_store is not None:
                full, cow_src, matched, prepinned = self._fault_back_matched(
                    full, cow_src, matched, protect=spilled_vids
                )
                pinned.extend(sorted(prepinned))
                # a no-op can_alloc check unless a fault-back truncated
                # the match (impossible after the reservation above, but
                # the degradation path stays honest)
                need = pages_needed(p, self.page_size) - len(full)
                if not self._try_reserve(need):
                    self._release_pages(pinned)
                    break
            del self.queue[qi]
            fresh = self.alloc.alloc(need)
            pages = full + fresh
            if cow_src is not None:
                # fresh[0] becomes the private copy-on-write page holding
                # the partial tail of the matched prefix
                dst = fresh[0]
                self.pool = self._copy_fn(
                    self.pool,
                    *jax.device_put((np.int32(cow_src), np.int32(dst))),
                )
                self.copy_dispatches += 1
                self._release_pages([cow_src])  # back to cold (or shared)
            n_pages = len(pages)
            self.slot_req[s] = req
            self.slot_pages[s] = list(pages)
            self.bt[s, :] = self._sentinel
            self.bt[s, :n_pages] = pages
            self.pooled_len[s] = matched
            self.done[s] = True  # not decodable until prefill completes
            self.prefilling[s] = matched < target
            self.emitted[s] = len(req.tokens)
            self.budget[s] = req.max_new_tokens
            self.eos[s] = req.eos_id
            self.seeds[s] = req.seed
            self.slot_ctx[s] = [int(t) for t in req.prompt]
            self.slot_registered[s] = len(full)
            self.slot_node[s] = full[-1] if full else PrefixIndex._ROOT
            self.prompt_tokens_total += p
            self.prompt_tokens_cached += matched
            req.cached_tokens += matched
            if self.hybrid:
                self._admit_state(s, req)
            req.admit_tokens = len(req.tokens)  # livelock-guard baseline
            now = self.clock()
            if req.admit_time is None:
                req.admit_time = now
            if not req.tokens and req.evictions == 0:
                # first admission of a fresh request: the wait it just
                # paid IS the queue delay (re-admissions are eviction
                # stall, tracked by telemetry's derived metrics)
                self.metrics.histogram("queue_delay_s").observe(
                    now - req.submit_time
                )
            self._emit(
                "admitted", rid=req.rid, t=now, slot=s, prompt_tokens=p,
                cached_tokens=matched, pages=n_pages,
            )
            if not self.prefilling[s]:
                # a block-diffusion prompt with no whole block left to
                # prefill (shorter than a block, or all of it cached)
                if req.first_token_time is None:
                    req.prefill_done_time = now
                self._open_first_block(s)
            admitted += 1

    def _admit_state(self, s: int, req: Request) -> None:
        """A model with linear-attention layers: slot ``s``'s recurrent
        state starts over with ``req`` — at its first chunk, from zeros, at
        no dispatch of its own — and everything before the first token is
        prefilled again, whatever the pages may hold."""
        self.fresh[s] = True
        self.state_resets += 1
        if req.evictions:
            self.state_reprefill_tokens += int(req.prompt.size)
        if self.prefix_cache and req.prompt.size >= self.page_size:
            first = req.prompt[: self.page_size].tobytes()
            if first in self._first_pages:
                self.prefix_hits_refused += 1
            elif len(self._first_pages) < 65536:
                self._first_pages.add(first)

    # -- chunked prefill ----------------------------------------------------

    def _dispatch_chunk(
        self, s: int, toks: np.ndarray, start: int, clen: int,
        bt_row: np.ndarray,
    ) -> None:
        """Enqueue one prefill-chunk program for slot ``s``: ``toks``
        (``[1, bucket]``, right-padded) from position ``start``, ``clen``
        of them real, over the block-table row ``bt_row`` — a host array
        the caller no longer writes. Everything the host hands the program
        goes to the device in ONE ``jax.device_put``, the three scalars as
        ``np.int32`` among the arrays (``jnp.asarray(<python int>,
        jnp.int32)`` launches a ``jit(convert_element_type)`` program
        each), and nothing is read back: a chunk's results are the pool's
        rows and the slot's logits row, which stay on the device. A model
        with linear-attention layers also hands over the recurrent state
        and whether this is the request's first chunk; slot ``s`` is no
        longer fresh behind it."""
        bucket = toks.shape[1]
        if bucket not in self._chunk_fns:
            self._chunk_fns[bucket] = make_prefill_chunk_program(
                self.model,
                chunk_len=bucket,
                pmax=self.pmax,
                rope_len=self.block,
                mesh=self._mesh,
                prefill_sp=self.prefill_sp,
            )
        host = (np.int32(s), toks, np.int32(start), np.int32(clen), bt_row)
        if self.hybrid:
            host += (np.bool_(self.fresh[s]),)
            self.fresh[s] = False
        if self._ssm:
            self.ssm_rows_scanned += clen
            self.ssm_rows_run += bucket
        staged = jax.device_put(host)
        self.pool, self.logits, *st = self._chunk_fns[bucket](
            self.model, self.pool, self.logits, *staged[:5],
            *((self.state,) if self.hybrid else ()), *staged[5:],
        )
        if st:
            self.state = st[0]

    def _prefill_target(self, p: int) -> int:
        """Rows of a ``p``-token prompt that prefill makes resident: all
        of it — or, for a block-diffusion model, its whole blocks (the
        ``p mod B`` tokens left over open the first generated block,
        already revealed)."""
        return p - p % self.block_len if self.block_len else p

    def _open_first_block(self, s: int) -> None:
        """Slot ``s``'s prompt blocks are resident: it decodes from the
        next window on, its first block holding the prompt's remainder
        revealed and everything else masked."""
        ctx = self.slot_ctx[s]
        rem = len(ctx) - self._prefill_target(len(ctx))
        self.blk_tok[s] = self.model.config.mask_token
        self.blk_tok[s, :rem] = ctx[len(ctx) - rem:]
        self.blk_rev[s] = np.arange(self.block_len) < rem
        self.blk_at[s] = -1
        self.done[s] = False

    def _prefill_one_chunk(self, s: int, clen: int, granules: int) -> None:
        """Run ONE chunk program for slot ``s`` over its next ``clen``
        prompt tokens, ``granules`` of the budget's; when they are the
        last, the slot's prompt is resident and it decodes from the next
        window on."""
        req = self.slot_req[s]
        assert req is not None and self.prefilling[s]
        # == req.prompt.size at admission (its whole blocks, for a
        # block-diffusion model)
        p = self._prefill_target(len(self.slot_ctx[s]))
        start = int(self.pooled_len[s])
        assert 1 <= clen <= p - start, (s, p, start, clen)
        # padded to a power of two of pages, which may run past the
        # prompt, the slot's last page and the rope table: the padded
        # rows write nothing (write_token_rows) and clip their positions
        bucket = self._prefill_bucket(clen)
        tele = self.telemetry
        with span(
            "midgpt.engine.prefill_dispatch", tele, "prefill_chunk",
            clock=self.clock, rids=(req.rid,), step=self.fault_step,
            rid=req.rid, slot=s, start=start, chunk=clen, bucket=bucket,
        ) as sp:
            toks = np.full((1, bucket), self.pad_id, np.int32)
            toks[0, :clen] = req.prompt[start : start + clen]
            # a COPY of the row: on the CPU backend a put of a
            # 64-byte-aligned host array shares its memory, the program
            # runs when the device gets to it, and an eviction later in
            # this very step resets ``bt[s]`` — the chunk's rows were
            # then dropped and its pages, already registered as a cached
            # prefix, never written
            self._dispatch_chunk(s, toks, start, clen, self.bt[s].copy())
        self.prefill_dispatches += 1
        self.prefill_chunks += granules
        self.prefill_tokens_computed += clen
        if tele is not None:
            tele.emit(
                "prefill_chunk", step=self.fault_step, t=sp.t0 + sp.dur,
                rid=req.rid, slot=s, start=start, chunk=clen, bucket=bucket,
            )
        self.pooled_len[s] = start + clen
        self._register_pages(s)
        if start + clen >= p:
            self.prefilling[s] = False
            if req.first_token_time is None:
                # the reading the chunk's event carries, where one was
                # written: log and request then agree to the digit
                req.prefill_done_time = (
                    self.clock() if tele is None else sp.t0 + sp.dur
                )
            if self.role == "prefill":
                # disaggregated pools: the slot parks fully-prefilled
                # (done stays True, so no decode window ever carries
                # it) until the cluster exports its pages to a
                # decode-class engine
                self.handoff_ready[s] = True
            elif self.block_len:
                self._open_first_block(s)
            else:
                self.done[s] = False  # decodable from the next window on

    def _run_prefills(self) -> None:
        """Sarathi-style chunk scheduling: round-robin one granule of
        ``prefill_chunk`` tokens (or the prompt's remainder) per prefilling
        slot until the per-window token budget is spent (the first granule
        always runs, so prefill can never starve). The rotation cursor
        persists ACROSS windows — with a budget of fewer granules than
        there are prefilling slots (one, by default, for ``window=1``, a
        verify dispatch or a block window), restarting at slot 0 every
        window would feed slot 0's whole prompt before a second prefilling
        slot saw its first granule, exactly the TTFT starvation chunking
        exists to bound.

        The budget is handed out first; then each slot's granules of this
        step, which are consecutive in its prompt, run as ONE chunk
        program, slot by slot in the order of their first granule — up to
        ``max(prefill_chunk, PREFILL_MERGE_ROWS)`` rows a program, past
        which a next one begins. ``prefill_chunks`` counts the granules,
        ``prefill_dispatches`` the programs; a step that runs any counts
        once in ``prefill_steps``."""
        if not self.prefilling.any():
            return
        self.prefill_steps += 1
        left = {
            s: self._prefill_target(len(self.slot_ctx[s]))
            - int(self.pooled_len[s])
            for s in range(self.slots) if self.prefilling[s]
        }
        budget = (
            float("inf") if self.prefill_budget is None
            else self.prefill_budget
        )
        granules: tp.Dict[int, tp.List[int]] = {}  # in first-granule order
        spent = 0
        while left and (not granules or spent < budget):
            for s in sorted(
                left, key=lambda s: (s - self._prefill_rr) % self.slots
            ):
                g = (
                    left[s] if self.prefill_chunk is None
                    else min(self.prefill_chunk, left[s])
                )
                granules.setdefault(s, []).append(g)
                left[s] -= g
                if not left[s]:
                    del left[s]
                self._prefill_rr = (s + 1) % self.slots
                spent += g
                if spent >= budget:
                    break
        cap = max(self.prefill_chunk or 0, PREFILL_MERGE_ROWS)
        for s, gs in granules.items():
            runs: tp.List[tp.List[int]] = [[]]
            for g in gs:
                if runs[-1] and sum(runs[-1]) + g > cap:
                    runs.append([])
                runs[-1].append(g)
            for run in runs:
                self._prefill_one_chunk(s, sum(run), len(run))

    # -- prefix-index registration ------------------------------------------

    def _register_pages(self, s: int) -> None:
        """Index every newly-FULL page of slot ``s`` by its content chain.
        Full pages are immutable (append-only pool), so once indexed they
        may be aliased into any other block table."""
        if self.index is None:
            return
        ps = self.page_size
        ctx = self.slot_ctx[s]
        resident = int(self.pooled_len[s])
        while (self.slot_registered[s] + 1) * ps <= resident:
            i = self.slot_registered[s]
            page = int(self.bt[s, i])
            chunk = ctx[i * ps : (i + 1) * ps]
            canonical = self.index.register(self.slot_node[s], chunk, page)
            if canonical != page and self.index.is_spilled(canonical):
                # re-admission of a spilled prefix: identical content was
                # just recomputed into a resident page, so adopt OUR page
                # as the node (re-key, byte-identical by the chain hash)
                # and drop the host payload — no import dispatch needed
                self._spill_store.pop(canonical)
                self.index.unspill(canonical, page)
                self.spill_readmissions += 1
                canonical = page
            if canonical != page:
                # identical content was indexed first by someone else: our
                # page stays private (freed, not cached, at release) and
                # the chain continues through the canonical id — which we
                # must PIN (we hold no ref on it via slot_pages), or cold
                # LRU reclaim could free it while it is still this slot's
                # chain parent, leaving a dangling id in the index
                self.alloc.incref(canonical)
                self.index.revive(canonical)
                self.slot_pins[s].append(canonical)
            self.slot_node[s] = canonical
            self.slot_registered[s] += 1

    # -- release / eviction -------------------------------------------------

    def _release_slot(self, s: int) -> None:
        self._release_pages(self.slot_pages[s])
        self._release_pages(self.slot_pins[s])
        self.slot_pages[s] = []
        self.slot_pins[s] = []
        self.slot_req[s] = None
        self.bt[s, :] = self._sentinel
        self.pooled_len[s] = 0
        self.done[s] = True
        self.prefilling[s] = False
        self.handoff_ready[s] = False
        self.slot_ctx[s] = []
        self.slot_registered[s] = 0
        self.slot_node[s] = PrefixIndex._ROOT
        if self.block_len:
            # a block still to land goes with the slot: its tokens were
            # emitted, and a re-admission prefills them with the prompt
            self.blk_pend[s] = False

    def _evict(self, s: int, park: bool = False) -> None:
        """Preempt slot ``s``: keep its progress (prompt grows by the
        generated tokens, budget shrinks to the remainder) and re-queue it
        at the FRONT so it resumes as soon as pages free up. Its pages
        retire to the cold prefix cache, so re-admission typically
        re-prefills via cache hits — same tokens, a fraction of the
        FLOPs, and still bit-identical.

        Livelock guard: a request evicted ``park_threshold`` times in a
        row WITHOUT emitting a token since its admission is thrashing —
        two requests repeatedly trading the same pages would re-prefill
        each other forever — so it PARKS (``self.parked``) until a
        finish, a quarantine release, or an idle engine un-parks it,
        instead of spinning through admission again. ``park=True``
        (single-slot pool exhaustion) parks unconditionally. Parking
        rides the same progress-preserving path as eviction, so parked
        streams resume bit-identically too."""
        req = self.slot_req[s]
        assert req is not None
        progressed = len(req.tokens) > req.admit_tokens
        req.thrash = 0 if progressed else req.thrash + 1
        # rebuild from the ORIGINAL prompt (a second eviction appending to
        # an already-grown prompt would duplicate the first eviction's
        # tokens — caught in code review). prompt0 <= block - max_new, so
        # prompt0 + generated always fits block - remaining: no cropping,
        # and the continuation is identical to the un-evicted run
        req.prompt = np.concatenate(
            [req.prompt0, np.asarray(req.tokens, np.int32)]
        )
        req.evictions += 1
        self._emit(
            "evicted", rid=req.rid, slot=s, progressed=bool(progressed),
            evictions=req.evictions,
        )
        self._release_slot(s)
        self.evictions += 1
        if park:
            self.overload_parks += 1
            self.parked.append(req)
            self._emit("parked", rid=req.rid, reason="overload")
        elif req.thrash >= self.park_threshold:
            self.livelock_parks += 1
            self.parked.append(req)
            self._emit("parked", rid=req.rid, reason="livelock")
        else:
            self.queue.appendleft(req)

    def _unpark(self) -> None:
        """Release every parked request back onto the wait queue.
        Called when pages may have come back: a request finished, a
        fault-injected quarantine lifted, or the engine went otherwise
        idle (nothing else will ever free pages, so parked work must
        retry).

        Un-parking used to be blind FIFO; now (a) ordering is the
        admission selector's job — released requests re-enter the queue
        and ``_select_queued`` ranks them with everyone else (parked
        work always carries ``evictions > 0``, so it rides the resumed
        band and still beats fresh submissions, in park order), and
        (b) a parked request whose deadline passed while it waited is
        SHED here instead of re-queued — re-prefilling a request that
        can no longer meet its SLO would burn exactly the pages its
        peers are starved for (counted ``deadline_shed_requests``,
        evented ``deadline_shed`` with ``where="parked"``)."""
        now: tp.Optional[float] = None
        while self.parked:
            req = self.parked.pop(0)
            if req.deadline is not None:
                if now is None:
                    now = self.clock()
                if now > req.deadline:
                    self._expire(req, where="parked")
                    continue
            self._emit("resumed", rid=req.rid)
            req.queue_step = self.fault_step  # aging restarts at release
            self.queue.append(req)

    def _ensure_growth(self) -> None:
        """Before the window, every decoding slot needs pages for up to K
        more tokens; allocate on demand, evicting the youngest request (by
        admission recency ~ least progress) under pool pressure."""
        for s in self._decoding_slots():
            if self.slot_req[s] is None:
                continue  # evicted by an earlier slot's pressure this pass
            # growth is capped at the request's REMAINING budget, not the
            # raw window: near end-of-generation pooled_len + window can
            # point past the request's lifetime (and past the block
            # table), and demanding those pages would crash or evict
            # healthy requests for tokens that will never be written
            remaining = int(self.budget[s]) - int(self.emitted[s])
            tokens = int(self.pooled_len[s]) + min(self._grow, remaining)
            if self.block_len:
                # commits land whole blocks, never past the request's last
                tokens = min(
                    int(self.pooled_len[s]) + self._grow,
                    self._lifetime_tokens(
                        len(self.slot_ctx[s]), remaining
                    ),
                )
            need = min(
                pages_needed(tokens, self.page_size), self.pmax
            ) - len(self.slot_pages[s])
            parked_self = False
            while need > 0 and not self._try_reserve(need):
                others = [v for v in self._active_slots() if v != s]
                if not others:
                    # even the lone request cannot grow (fault-injected
                    # quarantine, or a pool transiently starved of cold
                    # pages): PARK it with progress kept instead of the
                    # old hard MemoryError — it resumes when pages come
                    # back, and overload shows up as a counter, not a
                    # crash
                    self._evict(s, park=True)
                    parked_self = True
                    break
                # least progress loses: cheapest re-prefill on re-admission
                self._evict(min(others, key=lambda v: len(self.slot_req[v].tokens)))
            if parked_self:
                continue
            self._add_pages(s, need)

    def _add_pages(self, s: int, need: int) -> None:
        """Append ``need`` newly allocated pages (reserved already) to
        slot ``s``'s pages and block table."""
        if need > 0:
            pages = self.alloc.alloc(need)
            start = len(self.slot_pages[s])
            self.slot_pages[s].extend(pages)
            self.bt[s, start : start + need] = pages

    # -- speculative drafting -----------------------------------------------

    def _draft(
        self, decoding: tp.List[int]
    ) -> tp.Tuple[np.ndarray, np.ndarray, tp.Optional[np.ndarray]]:
        """Host-side n-gram drafts for this verify dispatch: up to
        ``req.spec_k`` (the slot's ADAPTIVE draft length) guesses for the
        tokens FOLLOWING the pending next token, suffix-matched from the
        request's own prompt+generated history. Slots with no usable
        match ride with ``n_draft = 0`` — the dispatch degrades to plain
        one-token decode for them, never stalls them.

        Returns ``(drafts, n_draft, draft_probs)``; ``draft_probs`` is
        the dense ``[S, spec_len, V]`` draft distribution when the
        proposer is soft (SoftProposer protocol), else ``None`` — the
        default n-gram path is one-hot IN-PROGRAM and never builds it
        (zero rows are safe: every row past ``n_draft`` is masked out of
        acceptance and the residual carry)."""
        drafts = np.zeros((self.slots, self.speculate), np.int32)
        n_draft = np.zeros((self.slots,), np.int32)
        probs = (
            np.zeros(
                (self.slots, self.speculate, self.model.config.vocab_size),
                np.float32,
            )
            if self._soft_drafts
            else None
        )
        for s in decoding:
            req = self.slot_req[s]
            # clamp to the remaining budget: row 0 takes one of the
            # request's `remaining` tokens, so only remaining-1 drafts
            # can ever be emitted — rows past that would run the full
            # model and be discarded by the in-program budget mask
            remaining = int(self.budget[s]) - int(self.emitted[s])
            k = min(req.spec_k, self.speculate, remaining - 1)
            if k < 1:
                continue
            if probs is not None:
                # the request seed rides along: honest soft drafting
                # needs per-request entropy (see SoftProposer — a
                # ctx-only-derandomized "sample" is a point mass and
                # breaks rejection-sampling exactness across requests)
                got, q = self.proposer.propose_soft(
                    self.slot_ctx[s], k, req.seed
                )
                got = list(got)[: self.speculate]
                if len(got):
                    probs[s, : len(got)] = np.asarray(
                        q, np.float32
                    )[: len(got)]
            else:
                got = self.proposer.propose(self.slot_ctx[s], k)
                got = got[: self.speculate]
            drafts[s, : len(got)] = got
            n_draft[s] = len(got)
        return drafts, n_draft, probs

    def _adapt_spec(self, req: Request, drafted: int, accepted: int) -> None:
        """Per-request draft-length controller: track a trailing
        acceptance-rate EWMA and size the next draft to it — a request in
        a repetitive region climbs back to the full ``speculate``, one in
        novel text decays toward 1 (cheap single-draft probes keep the
        estimate live, so recovery is automatic)."""
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        req.spec_drafted += drafted
        req.spec_accepted += accepted
        if drafted < 1:
            return
        rate = accepted / drafted
        req.spec_rate = 0.5 * req.spec_rate + 0.5 * rate
        req.spec_k = max(
            1,
            min(
                self.speculate,
                int(round(1 + req.spec_rate * (self.speculate - 1))),
            ),
        )

    def _run_verify(self, decoding: tp.List[int]) -> None:
        """One speculative verify dispatch + harvest (the spec-mode
        replacement for the K-step decode window)."""
        tele = self.telemetry
        with span(
            "midgpt.engine.decode_dispatch", tele, clock=self.clock
        ) as disp:
            drafts, n_draft, draft_probs = self._draft(decoding)
            host = (
                self.bt, self.pooled_len, self.done, self.emitted,
                self.budget, self.eos, drafts, n_draft,
            )
            if self.temperature > 0.0:
                # sampled verify: per-slot request seeds + the engine's
                # base key — the program derives every categorical/
                # acceptance stream from (seed, stream position) alone, so
                # the same discipline that makes the plain sampled window
                # scheduling-invariant carries over to speculation
                # unchanged
                host += (self.seeds,)
                if draft_probs is not None:
                    host += (draft_probs,)
            staged = jax.device_put(host)
            if self.temperature > 0.0:
                # the key, the device's already, rides behind the seeds
                staged = (*staged[:9], self._key, *staged[9:])
            (
                self.pool, self.logits, cand, emit, done_d, new_len,
                emitted_d, n_acc,
            ) = self._verify_fn(self.model, self.pool, self.logits, *staged)
        self.verify_dispatches += 1

        with self._harvest_span(disp, "verify_dispatch", decoding) as hw:
            cand_h, emit_h, n_acc_h, *state = self._read_window(
                (cand, emit, n_acc, done_d, new_len, emitted_d)
            )
            self._harvest_state(*state)
            if tele is not None:
                hw.tokens = int(emit_h[decoding].sum())
                hw.data.update(
                    drafted=int(n_draft[decoding].sum()),
                    accepted=int(n_acc_h[decoding].sum()),
                )

        def harvested(s: int, req: Request) -> tp.List[int]:
            self._adapt_spec(req, int(n_draft[s]), int(n_acc_h[s]))
            return [
                int(cand_h[s, j])
                for j in range(self.speculate + 1)
                if emit_h[s, j]
            ]

        self._harvest_apply(hw, decoding, harvested)

    def _run_window(self, decoding: tp.List[int]) -> None:
        """One K-step decode window harvested: the window already in
        flight, or else one dispatched now for ``decoding``. Before the
        read, the next window goes in behind it wherever :meth:`_ahead`
        finds that the read can change nothing of it; that one stays in
        flight for the next step's harvest, so the host's read,
        bookkeeping, caller and scheduling run while the device computes
        it."""
        flight = self._flight or self._dispatch_window(decoding)
        self._flight = None
        with span("midgpt.engine.grow"):
            ahead = self._ahead(flight.decoding)
        if ahead:
            self._flight = self._dispatch_window(flight.decoding, flight.out)
        self._harvest_window(flight)

    def _settle(self) -> None:
        """Harvest the window in flight, if one is: what changes a slot's
        state between steps (cancel, drain, a handoff, a request that
        waits, a fault hook, a profiler session's edge) finds every window
        read."""
        if self._flight is not None:
            flight, self._flight = self._flight, None
            self._harvest_window(flight)

    def _ahead(self, decoding: tp.List[int]) -> bool:
        """Whether the next window may be enqueued behind the window in
        flight (which carries ``decoding``, and found the host state as it
        is now) before that one is read, its pages allocated if so. It may
        where the read can change nothing of it: no slot can end in the
        window in flight (more than ``window`` tokens of budget left, a
        slot that is not done emits one a step, and no EOS id), every slot
        decodes (none empty, prefilling or waiting for a handoff: a request
        sent before the next step could join no window sooner), nobody
        waits for a slot, and the pool gives every slot its pages for the
        next window without an eviction. Then each slot advances exactly
        ``window`` positions, and the next window takes the lengths,
        ``done`` and ``emitted`` of the one in flight as they are on the
        device. An engine with a fault hook keeps the plain order: a
        scripted fault lands where it always has."""
        if (
            not self._dispatch_ahead or self._fault_hook is not None
            or self.queue or self.parked
        ):
            return False
        if len(decoding) != self.slots:
            return False
        left = self.budget[decoding] - self.emitted[decoding]
        if np.any(left <= self.window) or np.any(self.eos[decoding] >= 0):
            return False
        need = {
            s: min(
                pages_needed(
                    int(self.pooled_len[s]) + self.window
                    + min(self.window, int(left[i]) - self.window),
                    self.page_size,
                ),
                self.pmax,
            ) - len(self.slot_pages[s])
            for i, s in enumerate(decoding)
        }
        total = sum(n for n in need.values() if n > 0)
        # cold prefix pages give way as they do to _ensure_growth; spilling
        # one would read the pool, which the window in flight still holds
        if total and not (
            self.alloc.can_alloc(total)
            or (self._spill_store is None and self._try_reserve(total))
        ):
            return False
        for s, n in need.items():
            self._add_pages(s, n)
        return True

    def _dispatch_window(
        self, decoding: tp.List[int], behind: tp.Optional[tp.List] = None
    ) -> _Flight:
        """Enqueue one K-step decode window. In: the seven host arrays the
        scheduler keeps (block tables, lengths, ``done``, ``emitted``,
        budgets, EOS ids, seeds) in ONE ``jax.device_put``; behind a
        window not yet read (``behind``, its device outputs) lengths,
        ``done`` and ``emitted`` are that window's outputs and the other
        four go in the put. Pool, logits, key and a recurrent state are
        the device's already. The block tables go as a copy: the host
        grows them for the next window while this one may still run, and
        a put may alias the host array (the CPU backend's does)."""
        with span(
            "midgpt.engine.decode_dispatch", self.telemetry,
            clock=self.clock,
        ) as disp:
            bt = self.bt.copy()
            if behind is None:
                bt, lens, done, emitted, budget, eos, seeds = jax.device_put(
                    (bt, self.pooled_len, self.done, self.emitted,
                     self.budget, self.eos, self.seeds), self._window_put,
                )
            else:
                done, lens, emitted = behind[2:5]
                bt, budget, eos, seeds = jax.device_put(
                    (bt, self.budget, self.eos, self.seeds), self._window_put
                )
            self.pool, self.logits, *out = self._window_fn(
                self.model, self.pool, self.logits, bt, lens, done, emitted,
                budget, eos, seeds, self._key,
                *((self.state,) if self.hybrid else ()),
            )
            if self.hybrid:
                self.state = out.pop()
                self.recurrent_slot_steps += (
                    self.window * len(decoding)
                    * self.model.config.linear_layers
                )
        return _Flight(disp, decoding, out, behind is not None)

    def _harvest_window(self, flight: _Flight) -> None:
        """ONE :meth:`_read_window` of a decode window's stacked ``[K, S]``
        tokens and emit mask, the three state arrays and a latent model's
        expert counters, then its slots' bookkeeping; pool, logits and
        recurrent state stay on the device."""
        disp, decoding, out, ahead = flight
        self.windows_ahead += ahead
        with self._harvest_span(
            disp, "decode_window", decoding, window=self.window,
            windows_ahead=int(ahead),
        ) as hw:
            toks_h, emit_h, done_h, len_h, emitted_h, *counters = (
                self._read_window(out)
            )
            self._harvest_state(done_h, len_h, emitted_h)
            if self.telemetry is not None:
                hw.tokens = int(emit_h[:, decoding].sum())
            for c in counters:
                # a latent model's expert layers (the leading dense layers
                # have no router): rows routed in the window's steps
                routed = int(c["expert_rows"])
                self.expert_rows_routed += routed
                self.expert_rows_dropped += int(c["expert_claims"]) - routed
                self.expert_rows_max += int(c["expert_rows_max"])
                self.experts_touched += int(c["experts_touched"])
                self.expert_layer_forwards += (
                    self.window * self.model.config.expert_layers
                )

        def harvested(s: int, req: Request) -> tp.List[int]:
            return [
                int(toks_h[r, s]) for r in range(self.window) if emit_h[r, s]
            ]

        self._harvest_apply(hw, decoding, harvested)

    def _run_block_window(self, decoding: tp.List[int]) -> None:
        """One window of block-diffusion forwards + harvest: tokens arrive
        by whole blocks, from the forwards that revealed a block's last
        position."""
        with span(
            "midgpt.engine.decode_dispatch", self.telemetry,
            clock=self.clock,
        ) as disp:
            carry, emits, counters = self._window_fn(
                self.model,
                self.pool,
                *jax.device_put((
                    self.bt, self.pooled_len, self.done, self.emitted,
                    self.budget, self.eos, self.blk_tok, self.blk_rev,
                    self.blk_at, self.blk_pend, self.blk_pend_tok,
                )),
            )
            self.pool = carry[0]

        with self._harvest_span(
            disp, "decode_window", decoding, window=self.window
        ) as hw:
            (toks_h, ats_h, comp_h, n_emit_h), state, counters = (
                self._read_window((emits, carry[1:], counters))
            )
            committed = state[0] - self.pooled_len
            self._harvest_state(state[1], state[0], state[2])
            # np.array (copy): the scheduler writes these in place
            (self.blk_tok, self.blk_rev, self.blk_at, self.blk_pend,
             self.blk_pend_tok) = (np.array(a) for a in state[3:8])
            if self.telemetry is not None:
                hw.tokens = int(n_emit_h[:, decoding].sum())
        forwards = int(counters["denoise_forwards"])
        landed = int(counters["commit_forwards"])
        self.denoise_forwards += forwards
        self.commit_forwards += landed
        self.tokens_revealed += int(counters["tokens_revealed"])
        self.blocks_committed += int(committed.sum()) // self.block_len
        # a slot-forward runs 2·B rows: its block, and the block it lands
        # or as many dead rows
        live = (forwards + landed) * self.block_len
        self.block_rows_live += live
        self.block_rows_run += forwards * 2 * self.block_len
        if "expert_rows" in counters:
            rows = counters["expert_rows"]  # [L, E]
            cfg = self.model.config
            claims = live * cfg.experts_per_token * cfg.n_layer
            self.expert_rows += rows
            self.expert_rows_routed += int(rows.sum())
            self.expert_rows_dropped += claims - int(rows.sum())
            self.expert_rows_max += int(counters["expert_rows_max"])
            self.experts_touched += int(counters["experts_touched"])
            self.expert_layer_forwards += self.window * cfg.n_layer

        eos_id = self.eos

        def harvested(s: int, req: Request) -> tp.List[int]:
            new: tp.List[int] = []
            for r in range(self.window):
                if not comp_h[r, s]:
                    continue
                gen = np.nonzero(ats_h[r, s] >= 0)[0][: n_emit_h[r, s]]
                block = [int(t) for t in toks_h[r, s, gen]]
                steps = [int(a) for a in ats_h[r, s, gen]]
                if eos_id[s] >= 0 and int(eos_id[s]) in block:
                    cut = block.index(int(eos_id[s])) + 1
                    block, steps = block[:cut], steps[:cut]
                new.extend(block)
                req.reveal_steps.extend(steps)
            return new

        self._harvest_apply(hw, decoding, harvested)

    def _harvest_span(
        self, disp: span, kind: str, decoding: tp.List[int], **stats
    ) -> span:
        """Counts the dispatch just enqueued and opens the span in which
        the host blocks on its outputs. Under tracing the record ``kind``
        runs from the dispatch's start (``disp``) to the end of these
        reads, the existing sync: tracing adds no device round-trip of its
        own."""
        self.decode_dispatches += 1
        self.windows += 1
        self.occupancy_sum += len(decoding)
        # how far the paged kernel's live-page walk engages: per decode
        # step, the pages the occupied slots hold against the pages their
        # block tables could (host state as it stood at the dispatch; no
        # device read)
        steps = stats.get("window", 1)
        walks = [
            self.bt[s, : pages_needed(int(self.pooled_len[s]), self.page_size)]
            for s in decoding
        ]
        self.kv_pages_walked += steps * sum(len(w) for w in walks)
        self.kv_pages_table += steps * self.slots * self.bt.shape[1]
        # a page several slots share (a prefix hit) is walked by each of
        # them and is one page
        self.kv_pages_distinct += steps * (
            len(np.unique(np.concatenate(walks))) if walks else 0
        )
        tele = self.telemetry
        return span(
            "midgpt.engine.harvest_wait", tele, kind, clock=self.clock,
            t0=disp.t0, step=self.fault_step,
            rids=(
                tuple(self.slot_req[s].rid for s in decoding)
                if tele is not None else ()
            ),
            **stats,
        )

    def _read_window(self, tree):
        """THE device->host read of the step loop, and the only place that
        counts one (``device_reads``): ONE ``jax.device_get`` of everything
        a window returned for the host — tokens, masks, the scheduler's
        state arrays, counters — as NumPy leaves. ``device_get`` of the
        whole tree starts every leaf's copy before it waits for the first;
        a read a leaf (``np.asarray(toks)``, then ``np.asarray(emit)``,
        ...) is a round trip each that starts when the one before has
        returned, ~0.7–1 ms of idle device a leaf (PERF.md PR 25, PR 28).
        The leaves may be read-only views of the device's buffers: what
        the scheduler writes in place is copied (:meth:`_harvest_state`)."""
        self.device_reads += 1
        return jax.device_get(tree)

    def _harvest_state(
        self, done: np.ndarray, new_len: np.ndarray, emitted: np.ndarray
    ) -> None:
        """The scheduler's per-slot state as the window left it, from the
        HOST arrays of :meth:`_read_window`. np.array (copy): those may be
        read-only, and the scheduler mutates these in place."""
        self.done = np.array(done)
        self.pooled_len = np.array(new_len, np.int32)
        self.emitted = np.array(emitted, np.int32)

    def _harvest_apply(
        self,
        hw: span,
        decoding: tp.List[int],
        harvested: tp.Callable[[int, Request], tp.List[int]],
    ) -> None:
        """The bookkeeping after the harvest ``hw``: each decoding slot's
        new tokens (``harvested(slot, request)``), its pages registered,
        and finished requests retired."""
        now = self.clock()
        tele = self.telemetry
        if tele is not None:
            self.metrics.histogram("dispatch_s").observe(hw.dur)
            tele.emit(
                hw.kind, step=self.fault_step, t=now,
                slots=len(decoding), tokens=hw.tokens,
            )
        with span("midgpt.engine.harvest_apply"):
            finished_any = False
            for s in decoding:
                req = self.slot_req[s]
                new = harvested(s, req)
                if new and req.first_token_time is None:
                    req.first_token_time = now
                req.tokens.extend(new)
                self.slot_ctx[s].extend(new)
                self.tokens_generated += len(new)
                # generated tokens fill pages too — register them so
                # shared-context traffic (multi-turn chat) hits on
                # earlier turns
                self._register_pages(s)
                if tele is not None:
                    tele.emit(
                        "tokens", step=self.fault_step, t=now, rid=req.rid,
                        n=len(new), total=len(req.tokens), slot=s,
                    )
                if self.done[s]:
                    self._finish_request(req, now, s)
                    finished_any = True
            if finished_any and self.parked:
                # freed pages: parked requests get another shot
                self._unpark()

    def _finish_request(self, req: Request, now: float, slot: int) -> None:
        """Retire a finished request from its slot and observe the
        finish-time histograms — TTFT/e2e always (the scheduler already
        holds both timestamps), per-token TBT only under tracing (it
        needs the telemetry token timeline)."""
        req.finish_time = now
        req.outcome = "finished"
        self._live.pop(req.rid, None)
        self.finished[req.rid] = req
        if req.first_token_time is not None:
            self.metrics.histogram("ttft_s").observe(
                req.first_token_time - req.submit_time
            )
        self.metrics.histogram("e2e_s").observe(now - req.submit_time)
        tele = self.telemetry
        if tele is not None:
            ts = tele.token_times(req.rid)
            h = self.metrics.histogram("tbt_s")
            for a, b in zip(ts, ts[1:]):
                h.observe(b - a)
            tele.emit(
                "finished", step=self.fault_step, t=now, rid=req.rid,
                tokens=len(req.tokens), evictions=req.evictions,
            )
        self._release_slot(slot)

    @property
    def has_work(self) -> bool:
        """Queued, parked, or in-flight requests remain (a window in
        flight keeps its requests in their slots until it is read)."""
        return bool(self.queue or self.parked or self._active_slots())

    def step(self) -> bool:
        """One scheduler window. Returns True while there is (or was) work.

        May raise a scripted :mod:`~midgpt_tpu.serving.faults` fault when
        a ``fault_hook`` is installed — always BEFORE any dispatch, so
        the engine's request state stays consistent and drainable.

        A window in flight (dispatched ahead by the step before) is the
        one this step's :meth:`_run_window` harvests: it carries every
        slot, none prefilling, and its pages are there, so scheduling,
        prefill and growth find nothing to do. Where a request waits for a
        slot, or a fault hook is installed, that window is read first."""
        self.fault_step += 1
        if self.telemetry is not None:
            # optional jax.profiler window (telemetry.profile_steps):
            # host-driven start/stop at step boundaries, no effect on
            # the compiled programs
            self.telemetry.maybe_profile(self.fault_step)
        if TraceAnnotation.is_enabled() != self._in_session:
            # a profiler session opened or closed since the last step:
            # asked once, here, before anything of the step reads
            # ``self.telemetry``
            self._session_edge()
        with span("midgpt.engine.step", step=self.fault_step):
            if self.queue or self.parked or self._fault_hook is not None:
                self._settle()
            with span("midgpt.engine.schedule"):
                if self._fault_hook is not None:
                    self._fault_hook(self)
                if (
                    self.parked and not self.queue
                    and not self._active_slots()
                ):
                    # nothing else can free pages — parked work must
                    # retry now
                    self._unpark()
                self._spill_prefetch()
                self._admit()
            self._run_prefills()
            decoding = self._decoding_slots()
            runnable = bool(decoding)
            if runnable:
                with span("midgpt.engine.grow"):
                    self._ensure_growth()
                    # eviction may have changed it
                    decoding = self._decoding_slots()
            if self.telemetry is not None:
                self._census(decoding)
            if not runnable:
                # progress was prefill-only (or nothing runnable yet)
                return self.has_work
            if decoding:
                if self.block_len:
                    self._run_block_window(decoding)
                elif self.speculate:
                    self._run_verify(decoding)
                else:
                    self._run_window(decoding)
            return True

    def _census(self, decoding: tp.List[int]) -> None:
        """The ``step`` event: where this step's window is (or would have
        been) dispatched, how many slots it carries, how many hold a
        request it does not carry (still prefilling, or prefilled and
        waiting to be handed off), how many hold none, and the requests
        waiting for a slot. Integers the scheduler holds; nothing for a
        step without work."""
        held = sum(req is not None for req in self.slot_req)
        if held or self.queue or self.parked:
            self._emit(
                "step", decoding=len(decoding),
                prefilling=held - len(decoding), empty=self.slots - held,
                queued=len(self.queue), parked=len(self.parked),
            )

    def _session_edge(self) -> None:
        """A profiler session is the request log's second switch. Open,
        and this engine was built without a log: it attaches one of its
        own for as long as the session lasts, with the beginning of every
        request then waiting or in a slot back-filled from the request's
        own stamps (true ``t``, marked ``backfill``). Either way the log
        joins ``midgpt_tpu.telemetry.session_logs()``, which keeps it when
        the engine is gone, and notes this step. Closed: the log notes the
        step, and one that was the session's is detached. A window in
        flight is harvested first, into the log that saw it dispatched."""
        self._settle()
        if not self._in_session:
            if self.telemetry is None:
                self.telemetry = EngineTelemetry(ring=SESSION_RING)
                self._session_log = True
                self._backfill()
            self.telemetry.open_session(self.fault_step)
        else:
            self.telemetry.close_session(self.fault_step)
            if self._session_log:
                self.telemetry, self._session_log = None, False
        self._in_session = not self._in_session

    def _backfill(self) -> None:
        """The beginning of every request this engine holds (``_live``:
        queued, parked or in a slot) into a log that attached after it:
        ``submit`` and ``queued``, ``admitted`` once it has been, and for
        one whose prompt is resident the ``prefill_chunk`` that made it
        so — each at the time the request itself carries."""
        in_slot = {self.slot_req[s].rid: s for s in self._active_slots()}
        for req in self._live.values():
            old = dict(rid=req.rid, backfill=True)
            n = int(req.prompt0.size)
            self._emit(
                "submit", t=req.submit_time, prompt_tokens=n,
                budget=int(req.max_new_tokens), **old,
            )
            self._emit(
                "queued", t=req.submit_time, prompt_tokens=n,
                tokens_emitted=0, **old,
            )
            if req.admit_time is None:
                continue
            slot = in_slot.get(req.rid)  # None: evicted, waiting again
            self._emit("admitted", t=req.admit_time, slot=slot, **old)
            if (
                slot is not None and not self.prefilling[slot]
                and req.prefill_done_time is not None
            ):
                self._emit(
                    "prefill_chunk", t=req.prefill_done_time, slot=slot,
                    **old,
                )

    def warm_prefill(self, max_tokens: int) -> tp.List[int]:
        """Pre-compile every prefill-chunk bucket a dispatch can take —
        all powers-of-two page counts up to the longest one: with
        monolithic prefill a trace of prompts up to ``max_tokens``
        (suffix) tokens; with chunking the most rows a slot's granules of
        one step merge into (``max(prefill_chunk, PREFILL_MERGE_ROWS)``,
        or fewer where the budget allows fewer), whatever ``max_tokens``
        says. With the prefix cache on, admissions prefill arbitrary
        SUFFIX lengths, so warming only the full-prompt buckets leaves
        compiles inside the measured region on exactly the
        cache-hit/chunked paths. Each bucket runs one pad-token no-op
        chunk: an all-sentinel block table drops the page writes, and the
        engine must be idle (slot 0's logits row is scratch). Returns the
        warmed bucket lengths."""
        assert not self._active_slots(), "warm_prefill needs an idle engine"
        if self.prefill_chunk is None:
            cap = max_tokens
        else:
            # a slot's granules but its last are whole and ran while the
            # budget was not yet spent: at most ceil(budget / chunk) of
            # them, and the first always runs
            c = self.prefill_chunk
            cap = min(
                max(c, PREFILL_MERGE_ROWS),
                c * max(1, -(-self.prefill_budget // c)),
            )
        cap = min(cap, self.pmax * self.page_size)
        buckets = sorted(
            {self._prefill_bucket(n) for n in range(1, cap + 1)}
        )
        sentinel_row = np.full((self.pmax,), self._sentinel, np.int32)
        for b in buckets:
            # (slot 0's state is scratch too: admission resets it)
            self._dispatch_chunk(
                0, np.full((1, b), self.pad_id, np.int32), 0, b, sentinel_row
            )
        return buckets

    def clear_prefix_cache(self) -> int:
        """Reclaim every COLD cached page (refcount-0 resident prefixes)
        AND forget every host-spilled prefix; returns the total dropped.
        Live slots' pages are untouched. Benchmarks call this after
        warmup so measured hit rates (and spill counts) come from the
        measured trace alone."""
        n = 0
        if self.hybrid:
            self._first_pages.clear()
        if self.index is None:
            return n
        # spilled nodes first: they hang below cold resident pages, and
        # evict_cold_leaf skips any page with children (even virtual)
        if self._spill_store is not None:
            while True:
                vid = self.index.discard_spilled_oldest()
                if vid is None:
                    break
                self._spill_store.pop(vid)
                n += 1
        while True:
            victim = self.index.evict_cold_leaf()
            if victim is None:
                break
            self.alloc.reclaim(victim)
            n += 1
        return n

    def run(self, max_windows: int = 100_000) -> tp.Dict[int, Request]:
        """Drive :meth:`step` until queue and slots drain; returns the
        finished requests by id."""
        try:
            for _ in range(max_windows):
                if not self.has_work:
                    break
                self.step()
            else:
                raise RuntimeError(
                    f"engine did not drain in {max_windows} windows"
                )
        finally:
            if self.telemetry is not None:
                # a workload draining before the configured profiler
                # stop step must still finalize the trace
                self.telemetry.stop_profiling()
        return self.finished

    # -- reporting ----------------------------------------------------------

    def metrics_snapshot(self) -> tp.Dict[str, tp.Any]:
        """The full JSON-exportable registry view (counters, labeled
        families, live gauges, fixed-bucket histograms) —
        :meth:`stats` is the stable façade selecting from the same
        registry (telemetry.ENGINE_STATS_KEYS contract)."""
        return self.metrics.snapshot()

    def flight_dump(
        self,
        reason: str,
        path: tp.Optional[str] = None,
        extra: tp.Optional[tp.Dict[str, tp.Any]] = None,
    ) -> tp.Dict[str, tp.Any]:
        """The flight-recorder artifact: the bounded event + dispatch
        rings (when tracing is on), the metrics snapshot, and the stats
        façade, as one JSON-able record — what the cluster's fault
        paths and bench_serving's whole-trace watchdog persist so a
        wedged run still yields a timeline (the r4/r5 lesson). Reads
        host-side state only; safe to call best-effort from another
        thread (the cold-failover case — see
        telemetry.EngineTelemetry.flight_payload)."""
        rec: tp.Dict[str, tp.Any] = {
            "reason": reason,
            "fault_step": self.fault_step,
            "stats": self.stats(),
            "metrics": self.metrics_snapshot(),
            "telemetry": (
                self.telemetry.flight_payload()
                if self.telemetry is not None
                else None
            ),
        }
        if extra:
            rec.update(extra)
        if path is not None:
            rec["path"] = os.path.abspath(path)
            write_json(path, rec)
        return rec

    def stats(self) -> tp.Dict[str, float]:
        cfg = self.model.config
        occ = self.occupancy_sum / max(1, self.windows * self.slots)
        return {
            "tp": self.tp,
            "decode_dispatches": self.decode_dispatches,
            # device->host reads of the step loop (_read_window): one a
            # decode dispatch, none a prefill chunk
            "device_reads": self.device_reads,
            # decode windows enqueued before the one in front of them was
            # read (_ahead): the engaged share is this / decode_dispatches
            "windows_ahead": self.windows_ahead,
            "prefill_dispatches": self.prefill_dispatches,
            # the budget's granules of prefill_chunk tokens the dispatches
            # ran: granules a dispatch = prefill_chunks / prefill_dispatches
            "prefill_chunks": self.prefill_chunks,
            # engine steps that ran at least one chunk program: programs a
            # prefill step = prefill_dispatches / prefill_steps
            "prefill_steps": self.prefill_steps,
            "copy_dispatches": self.copy_dispatches,
            "tokens_generated": self.tokens_generated,
            "windows": self.windows,
            "slot_occupancy": round(occ, 4),
            "evictions": self.evictions,
            "free_pages": self.alloc.free_pages,
            "cached_pages": self.alloc.cached_pages,
            "cold_reclaims": self.cold_reclaims,
            # cold-page host spill (spill="on"; all zero otherwise)
            "spilled_pages": self.spilled_pages,
            "spill_faultback_pages": self.spill_faultback_pages,
            "spill_prefetch_pages": self.spill_prefetch_pages,
            "spill_readmissions": self.spill_readmissions,
            "spill_discards": self.spill_discards,
            "spill_resident_pages": (
                len(self._spill_store)
                if self._spill_store is not None else 0
            ),
            "prompt_tokens_total": self.prompt_tokens_total,
            "prefill_tokens_saved": self.prompt_tokens_cached,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefix_hit_rate": round(
                self.prompt_tokens_cached / max(1, self.prompt_tokens_total),
                4,
            ),
            "tokens_per_dispatch": round(
                self.tokens_generated / max(1, self.decode_dispatches), 2
            ),
            "verify_dispatches": self.verify_dispatches,
            "spec_drafted_tokens": self.spec_drafted,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_acceptance_rate": round(
                self.spec_accepted / max(1, self.spec_drafted), 4
            ),
            # fault tolerance / overload degradation (serving.faults)
            "admission_rejected": self.admission_rejected,
            "reject_reasons": dict(self.reject_reasons),
            "shed_requests": self.shed_requests,
            "deferred_submits": self.deferred_submits,
            "livelock_parks": self.livelock_parks,
            "overload_parks": self.overload_parks,
            "parked_requests": len(self.parked),
            # front-door outcomes (serving.frontdoor): submitter
            # cancellations and pre-dispatch deadline sheds
            "cancelled_requests": self.cancelled_requests,
            "deadline_shed_requests": self.deadline_shed_requests,
            "faults_injected": self.faults_injected,
            # block-diffusion windows and the expert layer's routing in
            # them (_build_block_window; all zero for other models)
            "denoise_forwards": self.denoise_forwards,
            "commit_forwards": self.commit_forwards,
            "blocks_committed": self.blocks_committed,
            "tokens_revealed": self.tokens_revealed,
            "block_rows_live": self.block_rows_live,
            "block_rows_run": self.block_rows_run,
            "expert_rows_routed": self.expert_rows_routed,
            "expert_rows_dropped": self.expert_rows_dropped,
            "expert_rows_max": self.expert_rows_max,
            "experts_touched": self.experts_touched,
            "expert_layer_forwards": self.expert_layer_forwards,
            # the paged kernel's walk: pages read a decode step, summed,
            # and the block-table pages of the same steps
            "kv_pages_walked": self.kv_pages_walked,
            "kv_pages_table": self.kv_pages_table,
            # recurrent layers (RecurrentState; zero otherwise), and what
            # the two kinds of cache hold right now (the state as counted)
            "state_resets": self.state_resets,
            "state_reprefill_tokens": self.state_reprefill_tokens,
            "prefix_hits_refused": self.prefix_hits_refused,
            "recurrent_slot_steps": self.recurrent_slot_steps,
            "recurrent_state_bytes": (
                self.state.nbytes if self.hybrid else 0
            ),
            "kv_bytes_live": 0 if self.latent else (
                int(self.pooled_len.sum()) * 2 * (
                    self.pool.k.shape[0] * self.pool.k.shape[-1]
                    * self.pool.k.dtype.itemsize
                )
            ),
            # the walk's pages counted once each (a shared page is walked
            # by every slot that holds it), and a latent-attention model's
            # cache: its layers, and the resident bytes of the tokens the
            # block tables reference — latent and rotary key, a shared page
            # counted once
            "kv_pages_distinct": self.kv_pages_distinct,
            "latent_layers": cfg.kv_layers if self.latent else 0,
            "latent_bytes_live": self._latent_tokens_live() * (
                cfg.kv_layers * (cfg.latent_kv + cfg.latent_rope)
                * self.pool.k.dtype.itemsize
            ) if self.latent else 0,
            # a Mamba model's prefill chunks: rows that were a prompt's, and
            # rows the scan ran
            "ssm_rows_scanned": self.ssm_rows_scanned,
            "ssm_rows_run": self.ssm_rows_run,
        }

    def _latent_tokens_live(self) -> int:
        """Tokens resident in the pages the slots' block tables reference,
        a page that several tables share counted once."""
        ps = self.page_size
        held = np.zeros((self.alloc.num_pages,), np.int64)
        for s in range(self.slots):
            n = int(self.pooled_len[s])
            first = ps * np.arange(pages_needed(n, ps))
            np.maximum.at(
                held, self.bt[s, : len(first)], np.minimum(ps, n - first)
            )
        return int(held.sum())


# Attach the registry-backed counter properties (data descriptors, so
# `engine.decode_dispatches += 1` and the bench's `setattr(e, name, 0)`
# reset both route through the registry's Counter objects).
for _name in _ENGINE_COUNTERS:
    setattr(ServingEngine, _name, _counter_property(_name))
del _name

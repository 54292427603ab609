"""kind ``serve_latent``: kind ``serve``'s closed loop round a ``ServingEngine``
that holds a latent-attention expert model (``joyai_llm_flash``), asked
questions over a few long documents that the prefix cache holds. The loop,
its clocks and its reduction are :mod:`benchmark.kinds.serve`'s; what is this
kind's own:

- the traffic: ``traffic.requests`` draws the QUESTIONS as it draws every
  cell's prompts (lengths and order from ``sizes_seed``, ids from the run's
  seed, no two starting alike); this kind draws ``documents`` documents of
  ``document_len`` ids from the run's seed and puts one in front of each
  question — every document once in each ``documents`` requests, in an order
  redrawn each time from ``sizes_seed`` — so that a prompt is a document and
  a question of its own, and a prefix hit is exactly the document's pages;
- set-up: bf16 weights from the seed for this architecture
  (:mod:`benchmark.weights_latent`), laid into the program's ``GPT`` (a stack
  of the leading dense layers and a stack of the expert layers); the chunk
  buckets and the decode window compiled; then each document asked once
  (document + one id the traffic never uses, one token out), which prefills
  it and leaves its pages with the prefix index for the whole run;
- ``serve_flops``: the forward passes behind the tokens the window emitted
  and the rows that were COMPUTED of the prompts whose first token arrived in
  it (a hit's document is not), by :mod:`benchmark.ops_latent`;
- the program's counters of the latent cache, the prefix cache and the expert
  layers, reduced to the numbers this cell's per-layer metrics read. A window
  in which a row was dropped, a request was evicted or a document lost a page
  counts one failed operation;
- ``correct``: as kind ``serve`` — once the engine is freed, ``check_requests``
  finished requests on different documents go, document, question and served
  tokens, through one full forward of the plain reference
  (:mod:`benchmark.reference_latent`: the published form, nothing absorbed,
  nothing cached), and the numbers compared are read from the gap by which each
  served token's logit lies below the reference's best.

The stand-ins, each of which has to come out not correct: ``ref_int8`` the
reference with int8-rounded operands (``ref_int4``: a test's, at a width
that barely feels int8) and ``wrong_scale`` the reference whose
softmax divides by the cached row's width (the token each puts first, at each
position of the engine's own text: a control need not decode);
``altered_token`` alters one served token; ``stale_page`` is the engine itself
with one page in the middle of every document overwritten, after set-up, by
the next document's page at the same place — what a wrong prefix hit or a
page freed under a reader would leave (an engine and a window of its own)."""

from __future__ import annotations

import sys
import time
import typing as tp

import numpy as np

from benchmark import ops_latent, program, reference_latent, weights_latent
from benchmark.kinds import serve

ANNOTATIONS = serve.ANNOTATIONS
STAND_INS_NEED_A_RUN: tp.Tuple[str, ...] = ("stale_page",)

_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
# the program's leaf for each of the benchmark's
_LEAF_OF = {"wte/weight": "wte", "ln_f/weight": "ln_f",
            "lm_head/weight": "lm_head"}
for _stack, _p in (("dense_blocks", "d_"), ("blocks", "e_")):
    _LEAF_OF.update({f"{_stack}/attn/{n}/weight": _p + n for n in _ATTN})
    _LEAF_OF.update({f"{_stack}/{n}/weight": _p + n for n in ("ln1", "ln2")})
_LEAF_OF.update({f"dense_blocks/mlp/{n}/weight": "d_" + n
                 for n in ("w_gate", "w_up", "w_down")})
_LEAF_OF.update({
    "blocks/mlp/router/weight": "e_router", "blocks/mlp/bias": "e_bias",
    "blocks/mlp/w_in": "e_w13", "blocks/mlp/w_out": "e_w2",
    "blocks/mlp/shared/w_gate/weight": "e_s_gate",
    "blocks/mlp/shared/w_up/weight": "e_s_up",
    "blocks/mlp/shared/w_down/weight": "e_s_down",
})
_STATS = ("expert_rows_routed", "expert_rows_dropped", "expert_rows_max",
          "experts_touched", "expert_layer_forwards", "kv_pages_walked",
          "kv_pages_distinct", "cold_reclaims")


def fill_model(w, mcfg):
    """The program's ``GPT`` holding the benchmark's arrays ``w``."""
    import jax

    from midgpt_tpu.models import GPT
    from midgpt_tpu.pytree import tree_paths

    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), mcfg))
    paths = tree_paths(shape)
    assert sorted(p for p, _ in paths) == sorted(_LEAF_OF), [p for p, _ in paths]
    for p, s in paths:
        assert w[_LEAF_OF[p]].shape == s.shape, (p, w[_LEAF_OF[p]].shape, s.shape)
    return jax.tree.unflatten(
        jax.tree.structure(shape), [w[_LEAF_OF[p]] for p, _ in paths])


class Cell(serve.Cell):
    def __init__(self, spec, seed, devices, annotate, stand_in=None):
        super().__init__(spec, seed, devices, annotate, stand_in)
        # at once, before anything is built: a program that lacks the
        # architecture refuses its configuration here
        self.mcfg = program.model_config(self.sizes, spec.get("program"))
        if not getattr(self.mcfg, "latent", False):
            raise ValueError(
                "kind serve_latent needs a program whose ModelConfig knows "
                "latent attention (attention='latent')")
        self.stale = stand_in == "stale_page"
        n, length = int(self.params["documents"]), int(self.params["document_len"])
        rng = np.random.default_rng([int(seed), 0xD0C5])
        self.docs = rng.integers(
            0, self.vocab - 1, size=(n, length)).astype(np.int32)
        self.doc_of: tp.List[int] = []  # record j's document
        self.requests = self._with_documents(self.requests)

    def _with_documents(self, questions):
        n = len(self.docs)
        fixed = np.random.default_rng(
            [int(self.params.get("sizes_seed", 0)), 0xD0C5])
        i = 0
        while True:
            if i % n == 0:
                order = fixed.permutation(n)
            r = next(questions)
            d = int(order[i % n])
            self.doc_of.append(d)
            yield {"prompt": np.concatenate([self.docs[d], r["prompt"]]),
                   "max_new_tokens": r["max_new_tokens"]}
            i += 1

    # -- set-up -------------------------------------------------------------

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp

        t = [time.perf_counter()]
        from midgpt_tpu.serving import ServingEngine

        t.append(time.perf_counter())
        model = jax.jit(lambda k: fill_model(
            weights_latent.make(k, self.sizes, jnp.bfloat16), self.mcfg))(
                self.key)
        self.engine = eng = ServingEngine(model, **self.engine_kw)
        del model
        jax.block_until_ready(eng.pool)
        t.append(time.perf_counter())
        chunk = int(self.engine_kw["prefill_chunk"])
        eng.warm_prefill(chunk)
        t.append(time.perf_counter())
        # one request of two chunks and two decode windows, on the id the
        # traffic never uses: compiles the decode window
        spare = self.vocab - 1
        eng.submit(np.full((chunk + 6,), spare, np.int32), 2 * eng.window)
        eng.run()
        eng.clear_prefix_cache()
        t.append(time.perf_counter())
        # each document's first ask: prefilled once, its pages the prefix
        # index's from here on
        for doc in self.docs:
            eng.submit(np.append(doc, np.int32(spare)), 1)
        eng.run()
        self.doc_pages = [self._pages_of(doc) for doc in self.docs]
        assert None not in self.doc_pages, "a document was not kept whole"
        if self.stale:
            # the planted fault: one page in the middle of every document
            # now holds the next document's rows of the same positions
            mid = len(self.doc_pages[0]) // 2
            held = [pages[mid] for pages in self.doc_pages]
            spare_page = eng.alloc.alloc(1)[0]
            for src, dst in zip(held + [spare_page], [spare_page] + held):
                eng.pool = eng._copy_fn(
                    eng.pool, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))
            eng.alloc.free([spare_page])
        jax.block_until_ready(eng.pool)
        t.append(time.perf_counter())
        self.setup_parts = dict(zip(
            ("program_imports_s", "weights_engine_s", "warm_prefill_s",
             "first_request_s", "documents_s"),
            (b - a for a, b in zip(t, t[1:]))))
        self.counters["decode_window"] = eng.window
        self.counters["experts"] = int(self.sizes["experts"])

    def _pages_of(self, doc) -> tp.Optional[tp.List[int]]:
        """The pages the prefix index holds ``doc`` in; None where it no
        longer holds every one."""
        full, _, matched = self.engine.index.match(doc)
        return list(full) if matched == len(doc) else None

    # -- the window's numbers ----------------------------------------------

    def _reduce(self, t0, t_close, stats0, live_log, tracer, emitted, flops):
        eng = self.engine
        cached = self.docs.shape[1]
        got = [r for r in self.records
               if r["first"] is not None and t0 <= r["first"] <= t_close]
        ctx = [r["plen"] + r["n"] / 2.0 for r in self.records if r["n"]]
        flops = emitted * ops_latent.row_forward_flops(
            self.sizes, float(np.mean(ctx)) if ctx else 0.0)
        flops += sum(ops_latent.prompt_flops(self.sizes, r["plen"], cached)
                     for r in got)
        stats1 = eng.stats()
        out = super()._reduce(t0, t_close, stats0, live_log, tracer, emitted,
                              flops)
        stats = self.counters["stats"]
        stats.update({k: stats1[k] - stats0[k] for k in _STATS})
        stats.update({k: stats1[k] for k in (
            "latent_bytes_live", "latent_layers", "cached_pages",
            "free_pages")})
        layer_forwards = max(1, stats["expert_layer_forwards"])
        touched = stats["experts_touched"] / layer_forwards
        live = self.counters["mean_live_tokens"]
        self.counters.update(
            expert_rows_dropped=stats["expert_rows_dropped"],
            expert_rows_mean=(stats["expert_rows_routed"] / layer_forwards
                              / self.counters["experts"]),
            expert_rows_max_mean=stats["expert_rows_max"] / layer_forwards,
            experts_touched_mean=touched,
            latent_bytes_share=ops_latent.latent_bytes_share(
                self.sizes, touched, live),
            shared_reread_share=1.0 - stats["kv_pages_distinct"] / max(
                1, stats["kv_pages_walked"]),
            prefix_hit_share=stats["prefill_tokens_saved"] / max(
                1, stats["prompt_tokens_total"]),
        )
        whole = all(self._pages_of(d) == p
                    for d, p in zip(self.docs, self.doc_pages))
        if stats["expert_rows_dropped"] or stats["evictions"] or not whole:
            out["failed"] += 1  # a dropped row, an eviction, a lost document
        print("engine counters over the window and its drain:", stats,
              file=sys.stderr, flush=True)
        return out

    # -- correct ------------------------------------------------------------

    def sample(self) -> tp.List[tp.Dict[str, tp.Any]]:
        """``check_requests`` finished requests on different documents, drawn
        from the seed."""
        done = [(r, self.doc_of[j]) for j, r in enumerate(self.records)
                if r.get("tokens")]
        rng = np.random.default_rng([int(self.seed), 0xC0DE])
        picked, seen = [], set()
        for i in rng.permutation(len(done)):
            r, d = done[i]
            if d not in seen:
                seen.add(d)
                picked.append(r)
        return picked[: int(self.spec.get("check_requests", 4))]

    def check(self, stand_in: tp.Optional[str] = None):
        """Kind ``serve``'s numbers, against this architecture's reference
        over this architecture's weights; the head on the served rows
        only."""
        import jax
        import jax.numpy as jnp

        limits = self.spec["limits"]
        names = ("served_logit_gap", "served_gap_mean", "served_flip_share")
        picked = self.sample()
        if not picked:
            return [(n, float("inf"), limits.get(n)) for n in names]
        length = int(self.spec.get("check_length", self.sizes["block_size"]))
        w = jax.jit(lambda k: weights_latent.make(
            k, self.sizes, jnp.bfloat16))(self.key)
        full = reference_latent.make_sequence_logits(self.sizes)
        low = None
        if stand_in and stand_in.startswith("ref_"):
            low = reference_latent.make_sequence_logits(
                self.sizes, quant=stand_in[4:])
        elif stand_in == "wrong_scale":
            low = reference_latent.make_sequence_logits(
                self.sizes, wrong_scale=True)

        @jax.jit
        def gaps(logits, nxt):
            best = jnp.max(logits, axis=-1)
            got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
            return best - got

        every = []
        for i, r in enumerate(picked):
            served = np.asarray(r["tokens"], np.int32)
            if stand_in == "altered_token" and i == 0:
                served = served.copy()
                j = len(served) // 2
                served[j] = (served[j] + 1) % (self.vocab - 1)
            seq = np.zeros((length,), np.int32)
            p, n = r["plen"], len(served)
            seq[:p], seq[p:p + n] = r["prompt"], served
            # row p - 1 + j predicts served token j
            at = jnp.arange(p - 1, p + n - 1)
            logits = full(w, jnp.asarray(seq), at)
            nxt = served
            if low is not None:
                nxt = np.asarray(jnp.argmax(low(w, jnp.asarray(seq), at), -1))
            every.append(np.asarray(gaps(logits, jnp.asarray(nxt))))
        g = np.concatenate(every).astype(np.float64)
        self.counters["checked_tokens"] = int(g.size)
        for leaf in jax.tree.leaves(w):
            leaf.delete()
        if not np.isfinite(g).all():
            g = np.full_like(g, np.inf)
        numbers = {"served_logit_gap": float(g.max()),
                   "served_gap_mean": float(g.mean()),
                   "served_flip_share": float((g > 0).mean())}
        return [(n, numbers[n], limits.get(n)) for n in names]


def build(spec, seed, devices, annotate, stand_in=None):
    return Cell(spec, seed, devices, annotate, stand_in)

"""kind ``serve_block``: kind ``serve``'s closed loop round a ``ServingEngine``
that holds a block-diffusion expert model (``sdar_moe``). The loop, its
clocks and its reduction are :mod:`benchmark.kinds.serve`'s; what is this
kind's own:

- set-up: bf16 weights from the seed for this architecture
  (:mod:`benchmark.weights_block`), laid into the program's ``GPT``;
- ``serve_flops``: the PUBLISHED loop's work for the tokens the window
  emitted — ``block_steps + 1`` forwards of ``block_len`` rows a block at the
  active parameter count, attention over the context, and one forward over
  each prompt whose first block arrived in the window
  (:mod:`benchmark.ops_block`) — whatever the program actually runs;
- the program's block and expert counters, reduced to the numbers this
  cell's per-layer metrics read;
- ``correct``: once the engine is freed, the longest finished request and
  ``check_requests - 1`` more go through the plain reference
  (:mod:`benchmark.reference_block`). The engine hands out, per token, the
  denoising step at which it was revealed; from the final tokens and those
  steps the state of every (block, step) is rebuilt, and all blocks of one
  step index are replayed in one forward (``[noisy ; clean]`` under the
  block-diffusion training mask). Compared, over every reveal of the whole
  generated blocks: how far the revealed token's logit lies below the
  reference's best at that position in that state (``served_logit_gap`` the
  widest, ``served_gap_mean``), and the reference's log-confidence of its
  surest still-masked position minus that of the position the engine
  revealed (``reveal_conf_gap`` the widest, ``reveal_conf_mean``).

The stand-ins put another chooser in the engine's place on the engine's own
text, state by state (the control need not decode): ``ref_int8`` the
reference with int8-rounded operands, ``ref_causal`` a model that is causal
inside the block, ``ref_kv_masked`` one whose context K/V come from the last
masked pass and not from the commit pass; ``altered_token`` alters one
served token. Each has to come out not correct."""

from __future__ import annotations

import sys
import time
import typing as tp

import numpy as np

from benchmark import ops_block, program, reference_block, weights_block
from benchmark.kinds import serve

ANNOTATIONS = serve.ANNOTATIONS
STAND_INS_NEED_A_RUN: tp.Tuple[str, ...] = ()

# the program's leaf for each of the benchmark's
_LEAF_OF = {
    "wte/weight": "wte", "blocks/attn/wqkv/weight": "wqkv",
    "blocks/attn/wo/weight": "wo", "blocks/attn/q_norm/weight": "q_norm",
    "blocks/attn/k_norm/weight": "k_norm", "blocks/ln1/weight": "ln1",
    "blocks/ln2/weight": "ln2", "blocks/mlp/router/weight": "router",
    "blocks/mlp/w_in": "w13", "blocks/mlp/w_out": "w2",
    "ln_f/weight": "ln_f", "lm_head/weight": "lm_head",
}
_BLOCK_STATS = (
    "denoise_forwards", "commit_forwards", "blocks_committed",
    "tokens_revealed", "expert_rows_routed", "expert_rows_dropped",
    "expert_rows_max", "experts_touched", "expert_layer_forwards")


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
        if not getattr(self.mcfg, "block_len", 0):
            raise ValueError("kind serve_block needs a block-diffusion model")

    # -- set-up -------------------------------------------------------------

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp

        t = [time.perf_counter()]
        from midgpt_tpu.serving import ServingEngine

        t.append(time.perf_counter())
        model = jax.jit(lambda k: fill_model(
            weights_block.make(k, self.sizes, jnp.bfloat16), self.mcfg))(
                self.key)
        self.engine = eng = ServingEngine(model, **self.engine_kw)
        del model
        jax.block_until_ready(eng.pool)
        t.append(time.perf_counter())
        chunk = self.engine_kw.get("prefill_chunk") or int(
            self.params["prompt_len"].get("max", 64))
        eng.warm_prefill(chunk)
        t.append(time.perf_counter())
        # one request of two chunks and two blocks, on the id the traffic
        # never uses: compiles the window
        warm = np.full((chunk + 6,), self.vocab - 1, np.int32)
        eng.submit(warm, 2 * self.mcfg.block_len)
        eng.run()
        eng.clear_prefix_cache()
        t.append(time.perf_counter())
        self.setup_parts = dict(zip(
            ("program_imports_s", "weights_engine_s", "warm_prefill_s",
             "first_request_s"), (b - a for a, b in zip(t, t[1:]))))
        self.counters["decode_window"] = eng.window
        self.counters["experts"] = int(self.sizes["experts"])

    # -- the window's numbers ----------------------------------------------

    def _reduce(self, t0, t_close, stats0, live_log, tracer, emitted, flops):
        eng = self.engine
        got = [r for r in self.records
               if r["first"] is not None and t0 <= r["first"] <= t_close]
        ctx = [r["plen"] + r["n"] / 2.0 for r in self.records if r["n"]]
        flops = ops_block.published_loop_flops(
            self.sizes, emitted, float(np.mean(ctx)) if ctx else 0.0)
        flops += sum(ops_block.prompt_flops(self.sizes, r["plen"]) for r in got)
        # the engine's own record of each finished request has the step at
        # which each token was revealed; no two prompts of a run are alike
        mine = {r.prompt0.tobytes(): r for r in eng.finished.values()}
        for r in self.records:
            if r.get("tokens"):
                r["steps"] = list(mine[r["prompt"].tobytes()].reveal_steps)
        stats1 = eng.stats()
        out = super()._reduce(t0, t_close, stats0, live_log, tracer, emitted,
                              flops)
        d = {k: stats1[k] - stats0[k] for k in _BLOCK_STATS}
        self.counters["stats"].update(d)
        layer_forwards = max(1, d["expert_layer_forwards"])
        self.counters.update(
            tokens_revealed=d["tokens_revealed"],
            slot_forwards=(self.counters["stats"]["decode_dispatches"]
                           * eng.window * eng.slots),
            expert_rows_dropped=d["expert_rows_dropped"],
            expert_rows_mean=(d["expert_rows_routed"] / layer_forwards
                              / self.counters["experts"]),
            expert_rows_max_mean=d["expert_rows_max"] / layer_forwards,
            experts_touched_mean=d["experts_touched"] / layer_forwards,
        )
        if d["expert_rows_dropped"]:
            out["failed"] += 1  # a dropless layer that dropped a row
        print("engine counters over the window and its drain:",
              self.counters["stats"], file=sys.stderr, flush=True)
        return out

    # -- correct ------------------------------------------------------------

    def check(self, stand_in: tp.Optional[str] = None):
        import jax
        import jax.numpy as jnp

        limits = self.spec["limits"]
        names = ("served_logit_gap", "served_gap_mean", "reveal_conf_gap",
                 "reveal_conf_mean")
        picked = self.sample()
        if not picked:
            return [(n, float("inf"), limits.get(n)) for n in names]
        sizes = self.sizes
        b, steps = int(sizes["block_len"]), int(sizes["block_steps"])
        mask_id = int(sizes["mask_token"])
        length = int(self.spec.get("check_length", sizes["block_size"]))
        assert length % b == 0, (length, b)
        w = jax.jit(lambda k: weights_block.make(k, sizes, jnp.bfloat16))(
            self.key)
        replay = reference_block.make_replay(sizes)
        kw = ({"quant": stand_in[4:]} if stand_in in ("ref_int8", "ref_int4")
              else {})  # the control: the precision below the stated one
        chooser_fn = (reference_block.make_replay(sizes, **kw)
                      if stand_in and stand_in.startswith("ref_") else None)
        every_logit, every_conf = [], []
        for i, r in enumerate(picked):
            served = np.asarray(r["tokens"], np.int32)
            at = np.asarray(r["steps"], np.int32)
            if stand_in == "altered_token" and i == 0:
                served = served.copy()
                j = len(served) // 2
                served[j] = (served[j] + 1) % (self.vocab - 1)
            p = r["plen"]
            whole = (p + len(served)) // b * b  # the last block may be cut
            seq = np.zeros((length,), np.int32)
            step_of = np.full((length,), -1, np.int32)
            seq[:p] = r["prompt"]
            seq[p:whole] = served[: whole - p]
            step_of[p:whole] = at[: whole - p]
            checked = np.zeros((length,), bool)
            checked[p:whole] = True
            readings, chooser = {}, None
            if chooser_fn is not None:
                chooser = {}
            for s in range(steps):
                toks, pos, mask = reference_block.replay_inputs(
                    seq, step_of, b, s, mask_id)
                want = seq
                if chooser_fn is not None:
                    c_toks, c_pos, c_mask = reference_block.replay_inputs(
                        seq, step_of, b, s, mask_id,
                        causal_inside=stand_in == "ref_causal",
                        context=("last_state" if stand_in == "ref_kv_masked"
                                 else "final"))
                    c_pick, c_conf, _, _ = chooser_fn(
                        w, jnp.asarray(c_toks), jnp.asarray(c_pos),
                        jnp.asarray(c_mask), jnp.asarray(seq))
                    chooser[s] = np.asarray(c_conf)
                    want = np.asarray(c_pick, np.int32)
                readings[s] = tuple(np.asarray(a) for a in replay(
                    w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(mask),
                    jnp.asarray(want)))
            lg, cg = reference_block.judge(
                step_of, checked, readings, b, b // steps, chooser)
            every_logit += lg
            every_conf += cg
        lg = np.asarray(every_logit, np.float64)
        cg = np.asarray(every_conf, np.float64)
        self.counters["checked_tokens"] = int(lg.size)
        for leaf in jax.tree.leaves(w):
            leaf.delete()
        if not lg.size or not (np.isfinite(lg).all() and np.isfinite(cg).all()):
            lg, cg = np.full((1,), np.inf), np.full((1,), np.inf)
        numbers = {"served_logit_gap": float(lg.max()),
                   "served_gap_mean": float(lg.mean()),
                   "reveal_conf_gap": float(cg.max()),
                   "reveal_conf_mean": float(cg.mean())}
        return [(n, numbers[n], limits.get(n)) for n in names]


def build(spec, seed, devices, annotate, stand_in=None):
    return Cell(spec, seed, devices, annotate, stand_in)

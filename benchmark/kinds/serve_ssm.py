"""kind ``serve_ssm``: kind ``serve``'s closed loop round a ``ServingEngine``
that holds a model of Mamba-1 and full-attention layers (``jamba``). The
loop, its clocks and its reduction are :mod:`benchmark.kinds.serve`'s; what
is this kind's own:

- set-up: bf16 weights from the seed for this architecture
  (:mod:`benchmark.weights_ssm`), laid into the program's ``GPT`` (one stack
  of blocks a kind of layer; the head is the embedding);
- ``serve_flops``: the forward passes behind the tokens the window emitted
  and the prompts whose first token arrived in it, by
  :mod:`benchmark.ops_ssm` (the matrices of both kinds of layer and the tied
  head, the scan's ``6 E N`` a Mamba layer a token, attention over the
  context in the attention layers only);
- the program's counters of the recurrent state and of the prefill scan's
  rows, reduced to the numbers this cell's per-layer metrics read;
- ``correct``: as kind ``serve`` — once the engine is freed, the longest
  finished requests go, prompt and served tokens, through one full forward of
  the plain reference (:mod:`benchmark.reference_ssm`: the scan a token at a
  time from a zero state), and the numbers compared are read from the gap by
  which each served token's logit lies below the reference's best.

The stand-ins, each of which has to come out not correct: ``ref_int8`` the
reference with int8-rounded operands (the token it puts first, at each
position of the engine's own text: the control need not decode);
``altered_token`` alters one served token; ``no_inner_norms`` is the
reference without Jamba's RMSNorms of dt, B and C (what leaving out that
piece of the mathematics would read); ``stale_state`` is the engine itself
run with admission's reset left out, so that a slot starts from its last
request's state (an engine and a window of its own).

A program whose ``ModelConfig`` holds no Mamba layer is refused in
``Cell.__init__``, before anything is built."""

from __future__ import annotations

import sys
import time
import typing as tp

import numpy as np

from benchmark import ops_ssm, program, reference_ssm, weights_ssm
from benchmark.kinds import serve

ANNOTATIONS = serve.ANNOTATIONS
STAND_INS_NEED_A_RUN: tp.Tuple[str, ...] = ("stale_state",)

_STATE_STATS = ("state_resets", "state_reprefill_tokens",
                "prefix_hits_refused", "recurrent_slot_steps",
                "ssm_rows_scanned", "ssm_rows_run")


def _leaf_of(path: str) -> str:
    """The benchmark's leaf for one of the program's: ``blocks/attn/wqkv/
    weight`` -> ``f_wqkv``, ``lin_blocks/attn/w_in/weight`` -> ``m_w_in``."""
    parts = [p for p in path.split("/") if p != "weight"]
    if parts[0] in ("blocks", "lin_blocks"):
        return ("f_" if parts[0] == "blocks" else "m_") + parts[-1]
    return parts[0]


def fill_model(w, mcfg):
    """The program's ``GPT`` holding the benchmark's arrays ``w``."""
    import jax

    from midgpt_tpu.models import GPT
    from midgpt_tpu.pytree import tree_paths

    shape = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), mcfg))
    names = [_leaf_of(p) for p, _ in tree_paths(shape)]
    assert sorted(names) == sorted(weights_ssm.LEAVES), names
    for n, s in zip(names, jax.tree.leaves(shape)):
        assert w[n].shape == s.shape, (n, w[n].shape, s.shape)
    return jax.tree.unflatten(jax.tree.structure(shape), [w[n] for n in names])


class Cell(serve.Cell):
    def __init__(self, spec, seed, devices, annotate, stand_in=None):
        super().__init__(spec, seed, devices, annotate, stand_in)
        # at once, before anything is built: a program that lacks the
        # architecture refuses its configuration here
        self.mcfg = program.model_config(self.sizes, spec.get("program"))
        if getattr(self.mcfg, "recurrent_mixer", "") != "mamba":
            raise ValueError(
                "kind serve_ssm needs a program whose ModelConfig knows "
                "Mamba layers (layer_types 'mamba')")
        self.stale = stand_in == "stale_state"

    # -- set-up -------------------------------------------------------------

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp

        t = [time.perf_counter()]
        from midgpt_tpu.serving import ServingEngine

        t.append(time.perf_counter())
        model = jax.jit(lambda k: fill_model(
            weights_ssm.make(k, self.sizes, jnp.bfloat16), self.mcfg))(
                self.key)
        self.engine = eng = ServingEngine(model, **self.engine_kw)
        del model
        jax.block_until_ready((eng.pool, eng.state))
        if self.stale:
            # the planted fault: no chunk is ever a request's first, so a
            # slot's state and tail start from what its last request left
            eng._admit_state = lambda s, req: None
        t.append(time.perf_counter())
        chunk = self.engine_kw.get("prefill_chunk") or int(
            self.params["prompt_len"].get("max", 64))
        eng.warm_prefill(chunk)
        t.append(time.perf_counter())
        # one request of two chunks and two decode windows, on the id the
        # traffic never uses: compiles the decode window
        warm = np.full((chunk + 6,), self.vocab - 1, np.int32)
        eng.submit(warm, 2 * eng.window)
        eng.run()
        eng.clear_prefix_cache()
        t.append(time.perf_counter())
        self.setup_parts = dict(zip(
            ("program_imports_s", "weights_engine_s", "warm_prefill_s",
             "first_request_s"), (b - a for a, b in zip(t, t[1:]))))
        self.counters["decode_window"] = eng.window
        self.counters["slots"] = eng.slots

    # -- the window's numbers ----------------------------------------------

    def _reduce(self, t0, t_close, stats0, live_log, tracer, emitted, flops):
        eng = self.engine
        got = [r for r in self.records
               if r["first"] is not None and t0 <= r["first"] <= t_close]
        ctx = [r["plen"] + r["n"] / 2.0 for r in self.records if r["n"]]
        flops = emitted * ops_ssm.row_forward_flops(
            self.sizes, float(np.mean(ctx)) if ctx else 0.0)
        flops += sum(ops_ssm.prompt_flops(self.sizes, r["plen"]) for r in got)
        stats1 = eng.stats()
        out = super()._reduce(t0, t_close, stats0, live_log, tracer, emitted,
                              flops)
        stats = self.counters["stats"]
        stats.update({k: stats1[k] - stats0[k] for k in _STATE_STATS})
        stats.update({k: stats1[k]
                      for k in ("recurrent_state_bytes", "kv_bytes_live")})
        self.counters["mean_live_slots"] = stats["slot_occupancy"] * eng.slots
        print("engine counters over the window and its drain:", stats,
              file=sys.stderr, flush=True)
        return out

    def free(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.engine.state):
            leaf.delete()
        super().free()

    # -- correct ------------------------------------------------------------

    def check(self, stand_in: tp.Optional[str] = None):
        """Kind ``serve``'s numbers, against this architecture's reference
        over this architecture's weights."""
        import jax
        import jax.numpy as jnp

        limits = self.spec["limits"]
        names = ("served_logit_gap", "served_gap_mean", "served_flip_share")
        picked = self.sample()
        if not picked:
            return [(n, float("inf"), limits.get(n)) for n in names]
        length = int(self.spec.get("check_length", self.sizes["block_size"]))
        w = jax.jit(lambda k: weights_ssm.make(
            k, self.sizes, jnp.bfloat16))(self.key)
        full = reference_ssm.make_sequence_logits(
            self.sizes, inner_norms=stand_in != "no_inner_norms")
        low = None
        if stand_in and stand_in.startswith("ref_"):
            low = reference_ssm.make_sequence_logits(
                self.sizes, quant=stand_in[4:])

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
            logits = full(w, jnp.asarray(seq))
            nxt = np.zeros((length,), np.int32)
            nxt[p - 1:p + n - 1] = served
            if low is not None:
                nxt = np.asarray(jnp.argmax(low(w, jnp.asarray(seq)), -1))
            every.append(np.asarray(
                gaps(logits, jnp.asarray(nxt)))[p - 1:p + n - 1])
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

"""kind ``serve``: one ``ServingEngine`` under a closed loop of clients.

Set-up makes bf16 weights from the seed, builds the engine, compiles the
prefill-chunk buckets (``warm_prefill``) and sends one short request through
to compile the decode window; the timed window then drives that same engine.
Each client sends its next request when its last one completes; times are the
harness's own clock around ``engine.step()``: sent, first token seen, last
token seen. The rate counts every output token the harness saw inside the
window; the tails are over the requests sent inside it. When the window
closes nothing more is sent and what is in flight is drained, so that every
request sent has its latencies.

``correct``: once the engine is freed, a sample of the finished requests
(the longest among them) goes, prompt and served tokens, through one full
forward of the plain reference; the numbers compared are read from the gap by
which each served token's logit lies below the reference's best."""

from __future__ import annotations

import time
import typing as tp

import numpy as np

from benchmark import ops, program, reference, traffic, weights


# the names this kind puts round its own calls (trace.Tracer.annotate)
ANNOTATIONS = r"^(engine\.|harvest\.)"


class Cell:
    def __init__(self, spec, seed: int, devices, annotate,
                 stand_in: tp.Optional[str] = None):
        self.spec, self.seed, self.annotate = spec, seed, annotate
        self.sizes = spec["sizes"]
        self.params = spec["traffic_params"]
        if self.params.get("loop", "closed") != "closed":
            raise ValueError("kind serve drives a closed loop only")
        self.engine_kw = dict(spec.get("engine", {}))
        if stand_in == "int8":
            # the control: the program's own lower-precision path
            self.engine_kw.update(quant="int8", kv_quant="int8")
        self.key = weights.key_of(seed)
        self.vocab = int(self.sizes["vocab_size"])
        self.requests = traffic.requests(self.params, seed, self.vocab)  # endless
        self.engine = None
        self.records: tp.List[tp.Dict[str, tp.Any]] = []
        self.spans: tp.List[tp.Dict[str, float]] = []
        self.counters: tp.Dict[str, tp.Any] = {}

    # -- set-up -------------------------------------------------------------

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp

        t = [time.perf_counter()]
        from midgpt_tpu.serving import ServingEngine

        t.append(time.perf_counter())
        mcfg = program.model_config(self.sizes, self.spec.get("program"))
        model = jax.jit(lambda k: program.fill_model(
            weights.make(k, self.sizes, jnp.bfloat16), mcfg))(self.key)
        self.engine = eng = ServingEngine(model, **self.engine_kw)
        del model
        jax.block_until_ready(eng.pool)
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

    # -- the timed window ---------------------------------------------------

    def run_window(self, seconds: float, tracer) -> tp.Dict[str, tp.Any]:
        """The closed loop: first ``ramp_steps`` engine steps untimed (set-up:
        the clients start together, and the window should see the engine as
        it is in the middle of a day; counted in steps so that every run's
        window starts at the same point of the trace), then the window, then
        the drain."""
        eng = self.engine
        clients = int(self.params["clients"])
        ramp = int(self.params.get("ramp_steps", 0))
        open_: tp.Dict[int, tp.Dict[str, tp.Any]] = {}
        live_log: tp.List[tp.Tuple[float, int]] = []
        clock = time.perf_counter
        t0 = closed_at = stats0 = None
        emitted, flops, steps = 0, 0.0, 0
        while True:
            now = clock()
            if t0 is None and steps >= ramp:
                t0, stats0 = now, eng.stats()
            if t0 is not None and closed_at is None:
                if now - t0 >= seconds:
                    closed_at = now
                    tracer.finish()
                else:
                    tracer.poll(now - t0)
            while closed_at is None and len(open_) < clients:
                r = next(self.requests)
                rec = {"plen": len(r["prompt"]), "prompt": r["prompt"],
                       "sent": clock(),
                       "timed": t0 is not None, "first": None, "last": None,
                       "n": 0, "done": None}
                self.records.append(rec)
                try:
                    with self.annotate("engine.submit"):
                        rid = eng.submit(r["prompt"], r["max_new_tokens"])
                except Exception as e:  # a refusal is a failed request
                    rec["error"] = repr(e)
                    if len([x for x in self.records if "error" in x]) > 64:
                        raise
                    continue
                rec["req"] = eng.lookup(rid)
                open_[rid] = rec
            if not open_:
                break
            live_log.append((clock(), sum(
                r["plen"] + r["n"] for r in open_.values() if r["n"])))
            t_s = clock()
            with self.annotate("engine.step"):
                eng.step()
            t = clock()
            steps += 1
            self.spans.append({"name": "engine_step", "t": t_s, "dur": t - t_s})
            in_window = t0 is not None and closed_at is None
            with self.annotate("harvest.poll"):
                for rid in list(open_):
                    rec = open_[rid]
                    n = len(rec["req"].tokens)
                    if n > rec["n"]:
                        if in_window:
                            # the forward passes behind what just arrived:
                            # the new tokens', and with a first token the
                            # whole prompt's
                            emitted += n - rec["n"]
                            flops += ops.forward_flops_of_sequence(
                                self.sizes,
                                rec["plen"] + rec["n"] if rec["n"] else 0,
                                rec["plen"] + n)
                        if rec["n"] == 0:
                            rec["first"] = t
                        rec["last"], rec["n"] = t, n
                    if rec["req"].done:
                        rec["done"] = t
                        rec["tokens"] = list(rec["req"].tokens)
                        del open_[rid], rec["req"]
            if closed_at is not None and clock() - closed_at > 60.0:
                break  # a request that never comes is a failed one
        return self._reduce(t0, closed_at, stats0, live_log, tracer, emitted,
                            flops)

    def _reduce(self, t0, t_close, stats0, live_log, tracer, emitted, flops):
        window_s = t_close - t0
        recs = [r for r in self.records if r["timed"]]
        finished = [r for r in recs if r["done"] is not None]
        failed = [r for r in recs if r["done"] is None]
        miss = 1e3 * (window_s + 60.0)  # a request that never answered

        def clear(a, b):
            # in a traced run the profiler's start and stop block the host
            # for seconds: a latency that spans one says nothing of the
            # engine, and is left out of the tails
            return not tracer.stalled(a, b)

        ttft = [1e3 * (r["first"] - r["sent"]) if r["first"] else miss
                for r in recs
                if not r["first"] or clear(r["sent"], r["first"])]
        tpot = [1e3 * (r["last"] - r["first"]) / (r["n"] - 1)
                for r in finished
                if r["n"] > 1 and clear(r["first"], r["last"])]
        tpot += [miss] * len(failed)
        stats1 = self.engine.stats()
        delta = {k: stats1[k] - stats0[k] for k in (
            "decode_dispatches", "prefill_dispatches", "copy_dispatches",
            "tokens_generated", "windows", "evictions",
            "prefill_tokens_computed", "prefill_tokens_saved",
            "prompt_tokens_total")}
        occ = (stats1["slot_occupancy"] * stats1["windows"]
               - stats0["slot_occupancy"] * stats0["windows"])
        delta["slot_occupancy"] = occ / max(1, delta["windows"])
        lo, hi = ((tracer.t_start, tracer.t_stop) if tracer.stopped
                  else (t0, t_close))
        live = [v for t, v in live_log if lo <= t <= hi and v] or [0]
        self.counters.update(
            stats=delta, chips=1, serve_flops=flops,
            window_s=window_s - tracer.stalled(t0, t_close),
            mean_live_tokens=float(np.mean(live)),
            requests_finished=len(finished), tokens_emitted=emitted,
            ttft_p95_ms=_p95(ttft), ttft_p50_ms=float(np.median(ttft)),
            tpot_p95_ms=_p95(tpot),
        )
        return {
            "attempted": len(recs), "failed": len(failed),
            "window_s": window_s, "t_start": t0,
            "end_to_end": {"serve_out_tok_s": emitted / window_s},
        }

    def free(self) -> None:
        import jax

        eng = self.engine
        for leaf in jax.tree.leaves((eng.model, eng.pool, eng.logits)):
            if hasattr(leaf, "delete"):
                leaf.delete()
        self.engine = None

    # -- correct ------------------------------------------------------------

    def sample(self) -> tp.List[tp.Dict[str, tp.Any]]:
        """The longest finished request and ``check_requests - 1`` others
        drawn from the seed."""
        done = [r for r in self.records if r.get("tokens")]
        if not done:
            return []
        n = int(self.spec.get("check_requests", 4))
        longest = max(range(len(done)),
                      key=lambda i: done[i]["plen"] + done[i]["n"])
        rng = np.random.default_rng([int(self.seed), 0xC0DE])
        rest = [i for i in rng.permutation(len(done)) if i != longest]
        return [done[i] for i in [longest] + rest[: n - 1]]

    def check(self, stand_in: tp.Optional[str] = None):
        """Per served token of the sample, the gap of its logit below the
        reference's best at that position: the widest, the mean, and the
        share of tokens that are not the reference's first choice. A number
        without a limit in the cell's file is printed, not judged."""
        import jax
        import jax.numpy as jnp

        limits = self.spec["limits"]
        names = ("served_logit_gap", "served_gap_mean", "served_flip_share")
        picked = self.sample()
        if not picked:
            return [(n, float("inf"), limits.get(n)) for n in names]
        length = int(self.spec.get("check_length", self.sizes["block_size"]))
        w = jax.jit(lambda k: weights.make(k, self.sizes, jnp.bfloat16))(
            self.key)
        full = reference.make_sequence_logits(self.sizes)
        low = None
        if stand_in and stand_in.startswith("ref_"):
            low = reference.make_sequence_logits(
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
                served[len(served) // 2] = (served[len(served) // 2] + 1) % (
                    self.vocab - 1)
            seq = np.zeros((length,), np.int32)
            p, n = r["plen"], len(served)
            seq[:p], seq[p:p + n] = r["prompt"], served
            logits = full(w, jnp.asarray(seq))
            nxt = np.zeros((length,), np.int32)
            nxt[p - 1:p + n - 1] = served
            if low is not None:
                # the control need not decode: the token the lower
                # precision puts first, at each position of the same text
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


# stand-ins that are the program itself with a path switched on: they need
# an engine and a window of their own
STAND_INS_NEED_A_RUN = ("int8",)


def _p95(vals: tp.Sequence[float]) -> float:
    """The 95th percentile by nearest rank."""
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(np.ceil(0.95 * len(s))) - 1)]


def build(spec, seed, devices, annotate, stand_in=None):
    return Cell(spec, seed, devices, annotate, stand_in)

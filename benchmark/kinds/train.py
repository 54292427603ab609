"""kind ``train``: the trainer's fused K-step window on the loader's feed.

The cell drives the pieces ``midgpt_tpu.train.train`` is made of, in its
order: ``resolve_auto_knobs``, ``create_mesh``, ``make_optimizer``, the
cached ``get_train_window`` program, ``Loader`` under ``PrefetchLoader``
with the trainer's transform, and one host read of the stacked losses per
window. (``train()`` itself cannot be stopped without a 12-byte-a-parameter
checkpoint and does not hand out its state: see PERF.md, Open questions.)

Set-up builds the state from the seed, sends it through the window program
twice (the first call compiles) and hands that same state and program to the
timed window. What the first call returned — each step's loss and gradient
norm, and per leaf the norm of Adam's first moment and of the parameters'
change — is what :func:`check` holds against the plain reference afterwards.
"""

from __future__ import annotations

import statistics
import time
import typing as tp

import numpy as np

from benchmark import ops, program, reference, weights


# the names this kind puts round its own calls (trace.Tracer.annotate)
ANNOTATIONS = r"^(train_window\.|prefetch\.)"


def _corpus(seed: int, n_tokens: int, vocab: int):
    rng = np.random.default_rng([int(seed), 0x7E57])
    return rng.integers(0, vocab, size=n_tokens, dtype=np.uint16)


class Cell:
    def __init__(self, spec, seed: int, devices, annotate):
        t = [time.perf_counter()]
        import jax
        from jax.sharding import PartitionSpec as P

        from midgpt_tpu.data import Loader, PrefetchLoader, Shard
        from midgpt_tpu.parallel.mesh import create_mesh
        from midgpt_tpu.parallel.sharding import make_global_array
        from midgpt_tpu.train import make_optimizer, resolve_auto_knobs

        t.append(time.perf_counter())
        self.spec, self.annotate = spec, annotate
        self.sizes = spec["sizes"]
        tr = spec["traffic_params"]
        self.k = int(tr["steps_per_dispatch"])
        self.batch = int(tr["batch_size"])
        self.t = int(self.sizes["block_size"])
        cfg = program.experiment_config(
            self.sizes, spec.get("program"),
            batch_size=self.batch, g_accum_iters=1,
            steps_per_dispatch=self.k, rundir="unused", data_dir="unused",
            data_seed=int(seed) & 0x7FFFFFFF, seed=int(seed) & 0x7FFFFFFF,
        )
        self.cfg = resolve_auto_knobs(cfg, len(devices))
        self.mesh = create_mesh(self.cfg.mesh, devices=list(devices))
        self.tx, _ = make_optimizer(self.cfg)
        self.key = weights.key_of(seed)
        self.step_key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)

        tokens = _corpus(seed, int(tr["corpus_tokens"]),
                         int(self.sizes["vocab_size"]))
        self.loader = Loader(
            shard=Shard(tokens=tokens, global_len=len(tokens), offset=0),
            block_size=self.t, batch_shape=(1, self.batch),
            seed=self.cfg.data_seed,
        )
        spec_ = P(None, None, ("replica", "fsdp"), "sequence")
        self.prefetch = PrefetchLoader(
            self.loader,
            transform=lambda x, y: (
                make_global_array(x, self.mesh, spec_),
                make_global_array(y, self.mesh, spec_),
            ),
            window=self.k,
        )
        t.append(time.perf_counter())
        self.setup_parts = {"program_imports_s": t[1] - t[0],
                            "config_mesh_loader_s": t[2] - t[1]}
        self.state = None
        self._ref = None
        self.first: tp.Dict[str, tp.Any] = {}
        self.spans: tp.List[tp.Dict[str, float]] = []
        self.counters: tp.Dict[str, float] = {}

    # -- set-up -------------------------------------------------------------

    def _make_state(self):
        import jax
        import jax.numpy as jnp

        from midgpt_tpu.models.gpt import gpt_param_rules
        from midgpt_tpu.parallel.sharding import constrain_params
        from midgpt_tpu.train import TrainState

        rules = gpt_param_rules(pipeline=False)
        pdtype = jnp.dtype(self.cfg.param_dtype)

        def init(key):
            model = program.fill_model(
                weights.make(key, self.sizes, pdtype), self.cfg.model)
            model = constrain_params(model, self.mesh, rules)
            opt = constrain_params(self.tx.init(model), self.mesh, rules)
            return TrainState(params=model, opt_state=opt,
                              step=jnp.zeros((), jnp.int32))

        return jax.jit(init)(self.key)

    def _probe(self, state):
        """Per leaf: the norm of Adam's first moment, and of the parameters'
        change since the seed's weights (rebuilt leaf by leaf, not kept)."""
        import jax
        import jax.numpy as jnp

        adam = [s for s in state.opt_state if hasattr(s, "mu")][0]

        def norms(params, mu, key):
            p, m = program.model_leaves(params), program.model_leaves(mu)
            out = {}
            for name in weights.LEAVES:
                p0 = weights.leaf(name, key, self.sizes, p[name].dtype)
                out["dp/" + name] = jnp.sqrt(jnp.sum(jnp.square(
                    p[name].astype(jnp.float32) - p0.astype(jnp.float32))))
                out["mu/" + name] = jnp.sqrt(jnp.sum(jnp.square(
                    m[name].astype(jnp.float32))))
            return out

        got = jax.jit(norms)(state.params, adam.mu, self.key)
        return {k: float(v) for k, v in got.items()}

    def warm(self) -> None:
        import jax

        from midgpt_tpu.train import get_train_window

        t = [time.perf_counter()]
        self.window_prog = get_train_window(self.cfg, self.mesh, self.k)
        self.state = self._make_state()
        self.prefetch.start()
        jax.block_until_ready(self.state)
        t.append(time.perf_counter())
        out = self._one_window()  # compiles; steps 0 .. K-1
        t.append(time.perf_counter())
        self.first = {
            "loss": [float(v) for v in out["loss"]],
            "grad_norm": [float(v) for v in out["grad_norm"]],
            **self._probe(self.state),
        }
        t.append(time.perf_counter())
        self._one_window()  # the warm call: set-up ends at its harvest
        t.append(time.perf_counter())
        self.spans.clear()
        self.setup_parts.update(zip(
            ("state_s", "first_window_s", "probe_s", "second_window_s"),
            (b - a for a, b in zip(t, t[1:]))))

    def _one_window(self):
        with self.annotate("prefetch.next"):
            xs, ys = self.prefetch.next()
        t0 = time.perf_counter()
        with self.annotate("train_window.launch"):
            self.state, out = self.window_prog(
                self.state, xs, ys, self.step_key)
        with self.annotate("train_window.harvest"):
            host = {k: np.asarray(v) for k, v in out.items()}
        t1 = time.perf_counter()
        self.spans.append({"name": "train_window", "t": t0, "dur": t1 - t0,
                           "k": self.k})
        return host

    # -- the timed window ---------------------------------------------------

    def run_window(self, seconds: float, tracer) -> tp.Dict[str, tp.Any]:
        steps = failed = 0
        t_start = time.perf_counter()
        t_end = t_start
        while t_end - t_start < seconds:
            tracer.poll(t_end - t_start)
            out = self._one_window()
            bad = int(np.sum(~np.isfinite(out["loss"])))
            failed += bad
            steps += self.k
            t_end = time.perf_counter()
        tracer.finish()
        window_s = t_end - t_start
        # the per-layer metrics' window leaves out the profiler's own calls
        # (a traced run only; the end-to-end rate is over the whole window)
        undisturbed_s = window_s - tracer.stalled(t_start, t_end)
        tokens = (steps - failed) * self.batch * self.t
        chips = len(self.mesh.devices.flat)
        self.counters.update(
            steps=steps, tokens=tokens, window_s=undisturbed_s, chips=chips,
            train_flops=tokens * ops.train_flops_per_token(self.sizes, self.t),
        )
        return {
            "attempted": steps, "failed": failed, "window_s": window_s,
            "t_start": t_start,
            "end_to_end": {"train_tok_s_chip": tokens / window_s / chips},
        }

    def free(self) -> None:
        self.prefetch.stop()
        import jax

        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
        self.window_prog = None

    # -- correct ------------------------------------------------------------

    def reference_numbers(self, quant=None, half=False, frozen=False):
        """The first K steps by the plain reference (or, with an argument
        set, by a stand-in for the program: a control, or a fault planted:
        the mean over ``half`` the rows, a state returned unchanged)."""
        import jax
        import jax.numpy as jnp

        rows = int(self.spec.get("reference_rows", 2))
        step = reference.make_train_step(
            self.sizes, _hyper(self.cfg), quant=quant, rows=rows,
            keep=self.batch // 2 if half else None, frozen=frozen)
        make = jax.jit(lambda k: weights.make(k, self.sizes, jnp.float32))
        w = make(self.key)
        mu = jax.tree.map(jnp.zeros_like, w)
        nu = jax.tree.map(jnp.zeros_like, w)
        losses, gnorms, mu1 = [], [], None
        for i in range(self.k):
            x, y = self.loader.peek(i)
            w, mu, nu, lo, gn = step(w, mu, nu, i, jnp.asarray(x[0]),
                                     jnp.asarray(y[0]))
            losses.append(float(lo))
            gnorms.append(float(gn))
            if i == 0:
                mu1 = {k: float(v) for k, v in reference.leaf_norms(mu).items()}
        w0 = make(self.key)
        dp = jax.jit(lambda a, b: reference.leaf_norms(
            {k: a[k] - b[k] for k in a}))(w, w0)
        out = {"loss": losses, "grad_norm": gnorms, "mu_first": mu1}
        out.update({"dp/" + k: float(v) for k, v in dp.items()})
        out.update({"mu/" + k: float(v)
                    for k, v in reference.leaf_norms(mu).items()})
        for tree in (w, mu, nu, w0):
            for leaf in jax.tree.leaves(tree):
                leaf.delete()
        return out

    def check(self, stand_in: tp.Optional[str] = None):
        if self._ref is None:
            self._ref = self.reference_numbers()
        ref = self._ref
        got = self.first
        if stand_in is not None:
            got = self.reference_numbers(**STAND_INS[stand_in])
        return compare(got, ref, self.spec["limits"])


STAND_INS = {
    # the control: the reference in the precision below the stated bf16
    "fp8": {"quant": "fp8"},
    # planted faults
    "half_batch": {"half": True},
    "frozen": {"frozen": True},
}


def _hyper(cfg):
    return {k: getattr(cfg, k) for k in (
        "learning_rate", "min_lr", "warmup_steps", "lr_decay_steps",
        "beta1", "beta2", "weight_decay", "grad_clip")}


def _worst_leaf(got, ref, prefix, skip=()):
    names = [k for k in weights.LEAVES if k not in skip]
    refs = {k: ref[prefix + k] for k in names}
    med = statistics.median(refs.values())
    return max(abs(got[prefix + k] - refs[k]) / max(refs[k], med)
               for k in names)


def compare(got, ref, limits) -> tp.List[tp.Tuple[str, float, float]]:
    """Each number compared, beside its limit. Leaves whose first gradient
    the reference finds under a thousandth of the median leaf's move by
    round-off alone under Adam: they are left out of the change."""
    med = statistics.median(ref["mu_first"].values())
    idle = [k for k, v in ref["mu_first"].items() if v < 1e-3 * med]
    numbers = {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got["loss"], ref["loss"])),
        "grad_norm_gap": max(abs(a - b) / abs(b) for a, b in
                             zip(got["grad_norm"], ref["grad_norm"])),
        "moment_leaf_gap": _worst_leaf(got, ref, "mu/"),
        "change_leaf_gap": _worst_leaf(got, ref, "dp/", skip=idle),
    }
    return [(k, float(v), limits.get(k)) for k, v in numbers.items()]


def build(spec, seed, devices, annotate, stand_in=None):
    return Cell(spec, seed, devices, annotate)

"""The serving programs the benchmark's cells run on the chip, as text:

    JAX_PLATFORMS=cpu python scripts/serving_jaxprs.py <checkout> <out_dir>

``str(jax.make_jaxpr(...))`` of every program of ``serve-xl-decode``,
``serve-sdar-block4``, ``serve-olmo-hybrid-decode`` and
``serve-joyai-flash-docs`` (each prefill-chunk bucket, the decode or the block
window; a cell whose configuration the checkout's ``ModelConfig`` cannot hold
is passed over) and of the verify program at ``speculate=4`` on ``midgpt-xl``,
traced from ``<checkout>`` through the engine's own ``make_*`` factories
with the cell's configuration and engine settings, ``paged_kernel="pallas"``
(what ``auto`` resolves to on a TPU), published widths, full depth (shapes
only: no weights, nothing compiles). Writes ``<out_dir>/<program>.txt`` and
prints a hash and a length per program: run it on ``git archive <parent>``
and on the change (each checkout's own copy of this script: it calls the
window with that checkout's signature), and ``diff`` the outputs, to show
that a change left what the chip runs as it was."""

import hashlib
import json
import os
import sys

root, out = os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2])
sys.path.insert(0, root)
os.chdir(root)
os.makedirs(out, exist_ok=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import program  # noqa: E402
from midgpt_tpu.models import GPT  # noqa: E402
from midgpt_tpu.serving import engine as eng  # noqa: E402
from midgpt_tpu.serving import paged  # noqa: E402
from midgpt_tpu.serving.paged import PagedKVPool, pages_needed  # noqa: E402

PAGE = 16  # ServingEngine's page_size default, where a cell sets no other
sds = jax.ShapeDtypeStruct
i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
flag = lambda *shape: sds(shape, jnp.bool_)  # noqa: E731


def emit(name, fn, *args):
    text = str(jax.make_jaxpr(fn)(*args)).replace(root, "<checkout>")
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write(text)
    print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text),
          flush=True)


for cell in ("serve-xl-decode", "serve-sdar-block4",
             "serve-olmo-hybrid-decode", "serve-joyai-flash-docs"):
    if not os.path.exists(f"benchmark/workloads/{cell}.json"):
        continue
    spec = json.load(open(f"benchmark/workloads/{cell}.json"))
    sizes = json.load(open(f"benchmark/configs/{spec['config']}.json"))
    try:
        cfg = program.model_config(sizes, spec.get("program"))
    except (AssertionError, TypeError, ValueError) as e:
        print(cell, "passed over:", repr(e)[:120], flush=True)
        continue
    hybrid = bool(getattr(cfg, "linear_layers", 0))
    latent = bool(getattr(cfg, "latent", False))
    if spec["kind"] == "serve_latent" and not latent:
        print(cell, "passed over: no latent attention here", flush=True)
        continue
    kw = spec["engine"]
    s, window = kw["slots"], kw.get("window", 4)
    PAGE = kw.get("page_size", 16)
    pmax = pages_needed(cfg.block_size, PAGE)
    model = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), cfg)),
    )
    pool = jax.eval_shape(lambda: PagedKVPool.init(cfg, kw["num_pages"], PAGE))
    logits = sds((s, cfg.vocab_size), jnp.float32)
    # a model with linear-attention layers: the recurrent state, last
    state = ((jax.eval_shape(lambda: paged.RecurrentState.init(cfg, s)),)
             if hybrid else ())
    geom = dict(pmax=pmax, rope_len=cfg.block_size, paged_kernel="pallas")
    # ServingEngine._prefill_bucket: pages rounded up to a power of two
    for pages in sorted({1 << (pages_needed(n, PAGE) - 1).bit_length()
                         for n in range(1, kw["prefill_chunk"] + 1)}):
        t = min(pages, pmax) * PAGE
        emit(f"{cell}.prefill_chunk_{t}",
             eng.make_prefill_chunk_program(
                 model, chunk_len=t, pmax=pmax, rope_len=cfg.block_size),
             model, pool, logits, i32(), i32(1, t), i32(), i32(), i32(pmax),
             *state, *((flag(),) if hybrid else ()))
    if cfg.block_len:
        b = cfg.block_len
        emit(f"{cell}.block_window",
             eng.make_block_window(model, slots=s, window=window, **geom),
             model, pool, i32(s, pmax), i32(s), flag(s), i32(s), i32(s),
             i32(s), i32(s, b), flag(s, b), i32(s, b), flag(s), i32(s, b))
        continue
    geom["temperature"] = kw["temperature"]
    emit(f"{cell}.decode_window",
         eng.make_decode_window(model, slots=s, window=window, **geom),
         model, pool, logits, i32(s, pmax), i32(s), flag(s), i32(s), i32(s),
         i32(s), i32(s), sds((2,), jnp.uint32), *state)
    if hybrid or latent:
        # no speculation without a rollback of the state, nor before the
        # verify rows have a latent form
        continue
    emit(f"{cell}.verify_spec4",
         eng.make_verify_program(model, slots=s, spec_len=4, **geom),
         model, pool, logits, i32(s, pmax), i32(s), flag(s), i32(s), i32(s),
         i32(s), i32(s, 4), i32(s))

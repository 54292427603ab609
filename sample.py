"""Sampling CLI (parity: /root/reference/sample.py).

    python sample.py --ckpt_dir=outputs/run [--start="text" | --start=FILE:f]
                     [--num_samples=3] [--max_new_tokens=200]
                     [--temperature=0.8] [--top_k=...] [--seed=0]

Loads config.json + the latest checkpoint from the rundir, tokenizes with
the dataset's meta.pkl char map if present else tiktoken GPT-2
(sample.py:143-159), and generates with the KV-cached sampler."""

from __future__ import annotations

import argparse
import json
import os
import pickle


def load_run_config(ckpt_dir: str):
    """Read <ckpt_dir>/config.json, via gcsfs for gs:// rundirs (parity:
    /root/reference/sample.py:39-46 — the reference switches to gcsfs when
    the dir is a bucket path; Checkpointer already handles gs:// itself)."""
    from midgpt_tpu.config import from_dict
    from midgpt_tpu.utils.fsio import open_path

    with open_path(os.path.join(ckpt_dir, "config.json")) as f:
        return from_dict(json.load(f))


def get_tokenizer(data_dir: str):
    meta_path = os.path.join(data_dir, "meta.pkl") if data_dir else ""
    if meta_path and os.path.exists(meta_path):
        with open(meta_path, "rb") as f:
            meta = pickle.load(f)
        stoi, itos = meta["stoi"], meta["itos"]
        # models whose vocab_size exceeds the charset (padded for MXU/TP
        # alignment) can emit unmapped ids when undertrained — render those
        # as U+FFFD instead of crashing the CLI
        return (
            lambda s: [stoi[c] for c in s],
            lambda ids: "".join(itos.get(int(i), "�") for i in ids),
        )
    try:
        import tiktoken

        enc = tiktoken.get_encoding("gpt2")
        return (
            lambda s: enc.encode(s, allowed_special={"<|endoftext|>"}),
            lambda ids: enc.decode([int(i) for i in ids]),
        )
    except Exception:
        # zero-egress fallback: raw token ids
        return (
            lambda s: [int(tok) for tok in s.split()],
            lambda ids: " ".join(str(int(i)) for i in ids),
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt_dir", required=True)
    ap.add_argument("--start", default="\n", help='prompt text or "FILE:path"')
    ap.add_argument("--num_samples", type=int, default=3)
    ap.add_argument("--max_new_tokens", type=int, default=200)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top_k", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    def _positive_int(v: str) -> int:
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return n

    ap.add_argument(
        "--serve", action="store_true",
        help="route generation through the continuous-batching serving "
        "engine (midgpt_tpu.serving): paged KV + fused K-step decode "
        "dispatch; one request per sample, early exit at --eos_id. "
        "NOTE: the engine's context is capped at block_size (prompts "
        "crop to block_size - max_new_tokens; no sliding window)",
    )
    ap.add_argument(
        "--serve_window", type=_positive_int, default=8,
        help="decode steps fused per XLA dispatch in --serve mode",
    )
    ap.add_argument(
        "--serve_page_size", type=_positive_int, default=16,
        help="KV page size (tokens) in --serve mode",
    )
    ap.add_argument(
        "--serve_prefill_chunk", type=_positive_int, default=None,
        help="chunked-prefill chunk size (tokens) in --serve mode; "
        "default monolithic",
    )
    ap.add_argument(
        "--serve_spec", type=_positive_int, default=None,
        help="self-speculative decoding draft length in --serve mode "
        "(n-gram prompt-lookup drafts verified in one dispatch; argmax "
        "acceptance at --temperature 0, rejection-sampling acceptance "
        "at --temperature > 0 — same stream contract either way). "
        "Default off.",
    )
    ap.add_argument(
        "--serve_tp", type=_positive_int, default=None,
        help="tensor-parallel degree in --serve mode: restore + serve on "
        "a tensor-only mesh over the first N devices (column/row-"
        "parallel weights, KV pool sharded by whole KV heads, vocab-"
        "sharded logits). 1 forces the single-chip engine on a "
        "multi-chip host. Default: the config mesh itself when it is "
        "serving-compatible (no sequence/pipeline axes — fsdp/replica "
        "restore sharding is preserved), else a tensor-only mesh at "
        "the config's tensor degree.",
    )
    ap.add_argument(
        "--no_prefix_cache", action="store_true",
        help="disable prefix-cache page sharing in --serve mode",
    )
    ap.add_argument(
        "--quant", choices=("int8",), default=None,
        help="serve the int8 per-channel quantized weight path "
        "(midgpt_tpu.quant): restores a pre-quantized params_q8 item "
        "when the checkpoint has one (scripts/quantize_ckpt.py), else "
        "quantizes the restored bf16 params on the fly; dequant is "
        "fused into every matmul, halving the per-token weight stream",
    )
    ap.add_argument(
        "--eos_id", type=int, default=None,
        help="stop a request early at this token id (--serve mode only)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.checkpoint import Checkpointer
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.sampling import make_sampler

    cfg = load_run_config(args.ckpt_dir)

    ckpt = Checkpointer(args.ckpt_dir, save_interval_steps=1)
    from midgpt_tpu.quant import QUANT_ITEM, abstract_quantized

    # pre-quantized serving checkpoint (scripts/quantize_ckpt.py): restore
    # the params_q8 item — the int8 weights land directly, no f32 staging
    use_q8 = bool(args.quant) and ckpt.has_item(QUANT_ITEM)
    import dataclasses

    from midgpt_tpu.models.gpt import pin_mlp_hidden_from_ckpt

    if not use_q8:
        # pre-256-rounding checkpoints hold the legacy fractional SwiGLU
        # width — pin to whatever the checkpoint actually stores (no-op
        # otherwise). A params_q8 checkpoint has no "params" metadata to
        # read; quantize_ckpt.py pins the width into its config.json
        cfg = dataclasses.replace(
            cfg, model=pin_mlp_hidden_from_ckpt(cfg.model, ckpt)
        )

    # params-only restore: checkpoints store params / opt_state as separate
    # items, so sampling never materializes Adam moments (the reference
    # rebuilds a dummy optimizer just to match the tree, sample.py:111-131)
    def init_fn(key):
        from midgpt_tpu.models.gpt import GPT

        return GPT.init(key, cfg.model)

    item = QUANT_ITEM if use_q8 else "params"
    abstract_params = (
        abstract_quantized(cfg.model)
        if use_q8
        else jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    )

    # multi-chip: restore straight into mesh shardings and decode
    # distributed (the reference replicates fully, sample.py:177-182).
    # --serve --serve_tp N picks a tensor-only SERVING mesh over the
    # first N devices (the geometry ServingEngine shards its KV pool
    # and programs on); otherwise the config's training mesh is used as
    # before. The rules match quantized leaves too (same `.../weight`
    # paths, plus the explicit `.../scale` rules splitting each
    # per-channel scale vector with its weight's out dim)
    mesh = None
    if args.serve:
        from midgpt_tpu.serving import serving_meshes

        if args.serve_tp:
            # explicit TP degree: tensor-only mesh over the first N
            # devices (None when N == 1 — the single-chip engine)
            mesh = serving_meshes(tp_size=args.serve_tp)[0]
        elif jax.device_count() > 1:
            # default: the config mesh itself WHEN the engine can serve
            # on it (no sequence/pipeline axes — fsdp/replica restore
            # sharding is preserved, the engine tolerates those axes as
            # replicated/contraction-sharded); a training config with
            # sequence/pipeline parallelism falls back to a tensor-only
            # mesh at its tensor degree (there is nothing to
            # sequence-shard one decode token deep)
            from midgpt_tpu.parallel.mesh import create_mesh

            try:
                mesh = create_mesh(cfg.mesh)
            except (AssertionError, ValueError):
                mesh = None
            if mesh is not None and (
                mesh.shape.get("sequence", 1) > 1
                or mesh.shape.get("pipeline", 1) > 1
            ):
                tp_deg = (
                    cfg.mesh.tensor
                    if 1 <= cfg.mesh.tensor <= jax.device_count()
                    else 1
                )
                mesh = serving_meshes(tp_size=tp_deg)[0]
    elif jax.device_count() > 1:
        from midgpt_tpu.parallel.mesh import create_mesh

        try:
            mesh = create_mesh(cfg.mesh)
        except (AssertionError, ValueError):
            mesh = None  # config mesh doesn't fit this host's devices
    if mesh is not None:
        from midgpt_tpu.models.gpt import GPT_PARAM_RULES
        from midgpt_tpu.parallel.sharding import param_shardings

        shardings = param_shardings(mesh, abstract_params, GPT_PARAM_RULES)
        abstract_params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract_params,
            shardings,
        )

    items, meta = ckpt.restore({item: abstract_params})
    model = items[item]
    print(
        f"restored step {meta['step']}"
        + (f" (pre-quantized {QUANT_ITEM})" if use_q8 else "")
        + f" from {args.ckpt_dir}"
    )

    encode, decode = get_tokenizer(cfg.data_dir)
    start = args.start
    if start.startswith("FILE:"):
        with open(start[5:]) as f:
            start = f.read()
    prompt = np.asarray(encode(start), dtype=np.int32)
    prompt = np.tile(prompt[None, :], (args.num_samples, 1))

    model = cast_floating(model, jnp.bfloat16)
    if args.quant:
        from midgpt_tpu.quant import is_quantized, quantize_model

        if not is_quantized(model):
            model = quantize_model(model)  # on-the-fly from a bf16 ckpt
    if args.serve:
        from midgpt_tpu.serving import generate_served

        outs = generate_served(
            model,
            [prompt[i] for i in range(args.num_samples)],
            args.max_new_tokens,
            eos_id=args.eos_id,
            temperature=args.temperature,
            top_k=args.top_k,
            window=args.serve_window,
            page_size=args.serve_page_size,
            prefix_cache=not args.no_prefix_cache,
            prefill_chunk=args.serve_prefill_chunk,
            speculate=args.serve_spec or 0,
            seed=args.seed,
            mesh=mesh,
        )
        for i in range(args.num_samples):
            print("-" * 40)
            print(start + decode(outs[i]))
        return
    sampler = make_sampler(
        args.max_new_tokens,
        mesh=mesh,
        temperature=args.temperature,
        top_k=args.top_k,
    )
    toks = sampler(model, jnp.asarray(prompt), jax.random.PRNGKey(args.seed))
    for i in range(args.num_samples):
        print("-" * 40)
        print(start + decode(np.asarray(toks[i])))


if __name__ == "__main__":
    main()

"""Convert ANY training checkpoint to the int8 quantized serving form.

    python scripts/quantize_ckpt.py --ckpt_dir outputs/run \
        --out outputs/run-int8 [--mode po2]

Restores the ``params`` item of the latest (or ``--step``) checkpoint in
``--ckpt_dir`` (params only — no optimizer state is read), converts every
dense matmul weight to the per-output-channel int8 pytree
(midgpt_tpu.quant.quantize_model), and writes a serving checkpoint to
``--out`` holding a single ``params_q8`` item plus the run's config.json
— loadable by ``sample.py --quant int8`` (and anything calling
``midgpt_tpu.quant.restore_quantized``) with the int8 arrays landing
directly, no full-precision staging.

``--mode po2`` (default) uses power-of-two scales: greedy serving output
is then bit-identical to serving the dequantized weights (the testable
exactness contract); ``--mode absmax`` keeps fractional scales (a ~1-bit
tighter grid, no bitwise contract)."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt_dir", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--mode", choices=("po2", "absmax"), default="po2")
    args = ap.parse_args()

    import dataclasses

    import jax

    from midgpt_tpu.checkpoint import Checkpointer
    from midgpt_tpu.config import to_dict
    from midgpt_tpu.models.gpt import (
        GPT,
        mlp_hidden_dim,
        pin_mlp_hidden_from_ckpt,
    )
    from midgpt_tpu.quant import QUANT_ITEM, quantize_model
    from sample import load_run_config

    cfg = load_run_config(args.ckpt_dir)
    ckpt = Checkpointer(args.ckpt_dir, save_interval_steps=1)
    cfg = dataclasses.replace(
        cfg, model=pin_mlp_hidden_from_ckpt(cfg.model, ckpt)
    )
    # pin the RESOLVED MLP width into the emitted config: the serving
    # checkpoint holds no "params" item, so a loader re-running the
    # fractional-width pin against it would have no metadata to read —
    # with the width explicit, pin_mlp_hidden_from_ckpt no-ops
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model, mlp_hidden=mlp_hidden_dim(cfg.model)
        ),
    )

    abstract = jax.eval_shape(
        lambda: GPT.init(jax.random.PRNGKey(0), cfg.model)
    )
    items, meta = ckpt.restore({"params": abstract}, step=args.step)
    step = int(meta["step"])
    print(f"restored step {step} from {args.ckpt_dir}")

    qmodel = quantize_model(items["params"], mode=args.mode)

    os.makedirs(args.out, exist_ok=True)
    out_ckpt = Checkpointer(args.out, save_interval_steps=1)
    saved = out_ckpt.save(
        step,
        {QUANT_ITEM: qmodel},
        {"step": step, "quant": "int8-per-channel", "quant_mode": args.mode},
        force=True,
    )
    if not saved:
        # Checkpointer.save no-ops (False) when the step already exists
        # — without this check a re-run with a different --mode would
        # print success while serving the OLD quantization
        raise SystemExit(
            f"--out {args.out} already holds step {step}; delete it or "
            "convert into a fresh directory"
        )
    out_ckpt.close()
    with open(os.path.join(args.out, "config.json"), "w") as f:
        json.dump(to_dict(cfg), f, indent=1)
    from midgpt_tpu.pytree import count_params

    n_int8 = sum(
        leaf.size
        for leaf in jax.tree.leaves(qmodel)
        if leaf.dtype == jax.numpy.int8
    )
    print(
        f"wrote {QUANT_ITEM} (mode={args.mode}) to {args.out}: "
        f"{n_int8 / 1e6:.1f}M int8 weights of "
        f"{count_params(qmodel) / 1e6:.1f}M total params"
    )


if __name__ == "__main__":
    main()

"""CLI launcher (parity: /root/reference/launch.py).

    python launch.py --config=shakespeare_char [--rundir=...] [--debug]
                     [--multihost] [--set key=value ...]

Improvements over the reference: any ExperimentConfig field can be
overridden from the CLI with --set (dotted paths reach nested configs,
e.g. --set model.n_layer=4 mesh.tensor=2); config provenance is dumped to
<rundir>/config.json and verified on resume via a model fingerprint.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


def _parse_value(s: str):
    try:
        return json.loads(s)
    except json.JSONDecodeError:
        return s


def apply_overrides(cfg, overrides):
    """dotted-path replace on nested frozen dataclasses."""
    for item in overrides:
        path, _, raw = item.partition("=")
        assert _, f"--set expects key=value, got {item!r}"
        value = _parse_value(raw)
        keys = path.split(".")

        def rec(obj, keys):
            if len(keys) == 1:
                return dataclasses.replace(obj, **{keys[0]: value})
            return dataclasses.replace(
                obj, **{keys[0]: rec(getattr(obj, keys[0]), keys[1:])}
            )

        cfg = rec(cfg, keys)
    return cfg


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True, help="named config")
    parser.add_argument("--rundir", default=None)
    parser.add_argument("--debug", action="store_true")
    parser.add_argument("--multihost", action="store_true")
    parser.add_argument(
        "--set", nargs="*", default=[], metavar="KEY=VALUE",
        help="config field overrides, dotted paths allowed",
    )
    args = parser.parse_args()

    import jax

    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.multihost:
        jax.distributed.initialize()  # (parity: launch.py:22-23)

    from midgpt_tpu.config import get_config, to_json

    cfg = get_config(args.config)
    cfg = apply_overrides(cfg, args.set)

    rundir = args.rundir or cfg.rundir
    if not rundir:
        assert not args.multihost, "--multihost requires an explicit --rundir"
        rundir = os.path.join("outputs", time.strftime("%Y%m%d-%H%M%S"))
    cfg = dataclasses.replace(cfg, rundir=rundir, debug=args.debug or cfg.debug)

    if jax.process_index() == 0:
        from midgpt_tpu.utils.fsio import open_path

        with open_path(os.path.join(rundir, "config.json"), "w") as f:
            f.write(to_json(cfg))
        print(to_json(cfg))

    if args.multihost:
        from jax.experimental.multihost_utils import sync_global_devices

        sync_global_devices("config_written")  # (parity: launch.py:69-70)

    from midgpt_tpu.train import train

    final = train(cfg)
    if jax.process_index() == 0:
        print("final:", json.dumps(final))


if __name__ == "__main__":
    main()

"""Everything the harness knows it finds by name: ``BENCHMARK.json`` at the
root of the checkout names the cells, configurations and metrics, and each
has a file of its own under ``benchmark/``. Adding one is adding files and
entries; nothing here lists them."""

from __future__ import annotations

import importlib
import json
import os
import typing as tp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


def _load(path: str) -> tp.Dict[str, tp.Any]:
    if not os.path.exists(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> tp.Dict[str, tp.Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> tp.Dict[str, tp.Any]:
    """The cell's entry of ``BENCHMARK.json`` merged over its parameter file
    ``benchmark/workloads/<name>.json``, with its configuration's sizes under
    ``sizes`` and the metrics it has to report under ``end_to_end`` /
    ``per_layer``."""
    bench = benchmark_json()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        have = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have: {have})")
    entry = entries[0]
    params = _load(os.path.join(HERE, "workloads", name + ".json"))
    for key in ("config", "chips"):
        if key in params and params[key] != entry[key]:
            raise SpecError(
                f"workloads/{name}.json says {key}={params[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}"
            )
    conf = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if not conf:
        raise SpecError(f"workload {name!r} names no known config")
    sizes = _load(os.path.join(ROOT, conf[0]["file"]))
    out = dict(params)
    out.update(name=name, config=entry["config"], chips=entry["chips"],
               traffic=entry["traffic"], sizes=sizes)
    out["end_to_end"] = [
        m for m in bench["end_to_end"]
        if name in m.get("workloads", [name])
    ]
    out["per_layer"] = []
    for m in bench["per_layer"]:
        if name in m.get("workloads", [name]):
            detail = _load(os.path.join(HERE, "metrics", m["name"] + ".json"))
            out["per_layer"].append({**detail, **m})
    return out


def kind(name: str):
    """``benchmark/kinds/<name>.py``: how one kind of cell is set up, driven
    and checked."""
    return importlib.import_module(f"benchmark.kinds.{name}")


def reader(name: str):
    """``benchmark/readers/<name>.py``: ``read(ctx, **args) -> float | None``."""
    return importlib.import_module(f"benchmark.readers.{name}").read

"""A later PR adds a cell, a configuration, a per-layer metric and a reader
as new files and new entries of BENCHMARK.json, and edits nothing that is
there: the harness finds each by its name."""

import hashlib
import json
import os

from benchmark.tests import tiny

READER = '''"""Steps per window, from the cell's own counters."""


def read(ctx, **_):
    steps = ctx["counters"].get("steps")
    windows = len([s for s in ctx["spans"] if s["name"] == "train_window"])
    return steps / windows if windows else None
'''


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d or ".jax_cache" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(fh.read()).hexdigest()
    return out


def test_new_cell_config_metric_and_reader_are_files_only(copy):
    bench_dir = os.path.join(copy, "benchmark")
    before = _digest(bench_dir)
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)

    sizes = dict(tiny.TINY_SIZES, n_layer=3)  # a new configuration
    cell = json.loads(json.dumps(tiny.TINY_TRAIN))
    cell["config"] = "tiny-l3"
    cell["traffic_params"]["batch_size"] = 2  # a new traffic mix: data only
    tiny.add_cell(copy, bench, "tiny-l3-train", "tiny-l3", sizes, cell)
    with open(os.path.join(bench_dir, "readers", "steps_per_window.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(bench_dir, "metrics", "steps_per_window.train.json"), "w") as f:
        json.dump({"reader": "steps_per_window", "args": {}}, f)
    bench["per_layer"].append({
        "name": "steps_per_window.train", "unit": "steps",
        "better": "higher", "source": "program_counter",
        "layer": "L1 entry train() window loop + host",
        "moves": "train_tok_s_chip", "workloads": ["tiny-l3-train"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-train" in m.get("workloads", []):
            m["workloads"].append("tiny-l3-train")
    tiny.write_bench(copy, bench)

    _, res, _ = tiny.run(copy, "tiny-l3-train", trace=1, seconds=1.0, seed=9)
    assert res["correct"] is True
    assert res["metrics"]["steps_per_window.train"]["value"] == 2.0
    assert "step_ms_p50.train" in res["metrics"]

    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tiny-l3.json", "metrics/steps_per_window.train.json",
        "readers/steps_per_window.py", "workloads/tiny-l3-train.json"]

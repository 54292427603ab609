"""A temporary copy of the benchmark with tiny cells added as files and
entries only — nothing that is there is edited — for the tests to run under
``--rehearsal`` on the CPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_SIZES = {
    "source": "test", "n_layer": 2, "n_head": 2, "n_embd": 64,
    "block_size": 128, "vocab_size": 512, "dropout": 0.0, "mlp": "gelu",
    "mlp_ratio": 4.0, "qk_norm": True, "tie_embeddings": False,
    "rope_base": 10000.0, "learning_rate": 1e-3, "min_lr": 1e-5,
    "warmup_steps": 10, "lr_decay_steps": 100, "beta1": 0.9, "beta2": 0.95,
    "weight_decay": 1e-4, "grad_clip": 1.0, "independent_wd": True,
    "param_dtype": "float32", "compute_dtype": "bfloat16", "reduced": [],
}

TINY_TRAIN = {
    "kind": "train", "config": "tiny", "chips": 1, "why": "test",
    "traffic_params": {"batch_size": 4, "steps_per_dispatch": 2,
                       "corpus_tokens": 65536},
    "program": {"loss_chunk": 64, "loss_chunk_unroll": True,
                "model": {"remat": "auto", "scan_unroll": 0,
                          "attn_impl": "naive"}},
    "reference_rows": 2, "trace": {"start_share": 0.2, "seconds": 0.5},
    # from CPU readings at this size (PERF.md, section 2): the program reads
    # at most 1.1e-4 / 9e-4 / 8e-4 / 2.6e-4, the fp8 control 4e-4 / 6e-3 /
    # 4e-3 / 1e-3
    "limits": {"loss_gap": 3e-4, "grad_norm_gap": 3e-3,
               "moment_leaf_gap": 2.4e-3, "change_leaf_gap": 8e-4},
}

TINY_SERVE = {
    "kind": "serve", "config": "tiny", "chips": 1, "why": "test",
    "traffic_params": {
        "loop": "closed", "clients": 2, "pool": 16, "sizes_seed": 0,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 48},
        "output_len": {"dist": "lognormal", "median": 32, "sigma": 0.4,
                       "min": 16, "max": 48},
        "distinct_first_token": True, "shared_prefix": 0,
        "ramp_steps": 4},
    "program": {"attn_impl": "naive"},
    "engine": {"slots": 2, "num_pages": 32, "prefill_chunk": 16,
               "temperature": 0.0},
    "check_requests": 12, "check_length": 96,
    "trace": {"start_share": 0.2, "seconds": 0.5},
    # CPU readings at this size over a dozen seeds and some 200 tokens each:
    # the program at most 0.019; the int8 reference 0.03 .. 0.2 (too near on
    # a seed in ten: a width of 64 barely feels int8), the int4 one over 0.5
    "limits": {"served_logit_gap": 0.04},
}


def make_copy(dst: str) -> str:
    """``dst`` becomes a checkout holding the benchmark, the program (by a
    link) and a ``BENCHMARK.json`` with two tiny cells added to it."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "midgpt_tpu"),
               os.path.join(dst, "midgpt_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    add_cell(dst, bench, "tiny-train", "tiny", TINY_SIZES, TINY_TRAIN)
    add_cell(dst, bench, "tiny-serve", "tiny", TINY_SIZES, TINY_SERVE)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            like = "train" if any(w.startswith("train") for w in m["workloads"]) else "serve"
            m["workloads"] = m["workloads"] + ["tiny-" + like]
    write_bench(dst, bench)
    return dst


def add_cell(dst, bench, name, config, sizes, workload):
    b = os.path.join(dst, "benchmark")
    with open(os.path.join(b, "configs", config + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(b, "workloads", name + ".json"), "w") as f:
        json.dump(workload, f)
    if not any(c["name"] == config for c in bench["configs"]):
        bench["configs"].append({
            "name": config, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{config}.json"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": name, "chips": 1, "why": "test"})


def write_bench(dst, bench):
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def run(dst, workload, *extra, seed=1, seconds=1.0, trace=0, check=True,
        rehearsal=True):
    """One rehearsal run in the copy; returns (exit code, result, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(dst, ".jax_cache")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *(["--rehearsal"] if rehearsal else []), *extra],
        cwd=dst, env=env, capture_output=True, text=True, timeout=600)
    if check and p.returncode != 0:
        raise AssertionError(p.stderr[-4000:])
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "null"
    return p.returncode, json.loads(last), p.stderr[-6000:]

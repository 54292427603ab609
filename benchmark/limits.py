"""Readings for setting a cell's limits, many seeds in one process (set-up is
most of a run):

    python3 -m benchmark.limits --workload <name> --seeds 1,2,3 \\
        [--stand-ins fp8,half_batch --stand-in-seeds 3] [--seconds 8]

For each seed: the program's numbers against the reference (the lower
reading), and for the first ``--stand-in-seeds`` seeds each stand-in's (a
control or a planted fault: the upper reading). One JSON line a reading."""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from benchmark import spec as specs
from benchmark import trace as tr
from benchmark.run import devices_for, enable_cache, say


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--stand-ins", default="")
    ap.add_argument("--stand-in-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    cell_spec = specs.cell(args.workload)
    devs = devices_for(int(cell_spec["chips"]), args.rehearsal)
    enable_cache()
    kind = specs.kind(cell_spec["kind"])
    stand_ins = [s for s in args.stand_ins.split(",") if s]
    own_engine = set(getattr(kind, "STAND_INS_NEED_A_RUN", ()))
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        todo = [None] + (stand_ins if i < args.stand_in_seeds else [])
        runs = [None] + [s for s in todo if s in own_engine]
        for run_as in runs:
            t0 = time.perf_counter()
            tracer = tr.Tracer(False, "", 0, 0)
            cell = kind.build(cell_spec, seed, devs,
                              lambda name: contextlib.nullcontext(), run_as)
            cell.warm()
            res = cell.run_window(args.seconds, tracer)
            cell.free()
            mine = [run_as] if run_as else [s for s in todo
                                            if s not in own_engine]
            for s in mine:
                compared = cell.check(s)
                print(json.dumps({
                    "workload": args.workload, "seed": seed, "as": s,
                    "failed": res["failed"], "attempted": res["attempted"],
                    "numbers": {n: v for n, v, _ in compared},
                    "seconds": time.perf_counter() - t0}), flush=True)
            del cell
    say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())

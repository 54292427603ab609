"""The reduction from trace to metrics, on a recorded trace: 3.1 s (two
windows of five steps) of ``train-xl-l8`` on one TPU v5e chip, taken by
``benchmark.run --trace 1 --out`` in PR 24. That run printed
``attn_roofline.train`` 27.5275, ``device_idle_share.train`` 0.1214,
``busy_s`` 3.096134437, ``window_s`` 3.099897343."""

import gzip
import json
import os
import shutil

import pytest

from benchmark import ops, run, spec
from benchmark import trace as tr
from benchmark.readers import (roofline, trace_idle_share,
                               trace_time_by_name)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    dst = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "train-xl-l8.xplane.pb.gz")) as f:
        with open(dst, "wb") as out:
            shutil.copyfileobj(f, out)
    return tr.TraceData.from_file(str(dst))


@pytest.fixture(scope="module")
def ctx(trace):
    cell = spec.cell("train-xl-l8")
    return {"trace": trace, "spans": [], "counters": {},
            "device_kind": "TPU v5 lite", "sizes": cell["sizes"]}, cell


def test_planes_and_lines(trace):
    ops_by_dev = trace.device_ops()
    assert list(ops_by_dev) == ["/device:TPU:0"]
    assert len(ops_by_dev["/device:TPU:0"]) == 9532
    modules = trace.select(tr.DEVICE_PLANE, "^XLA Modules$")["/device:TPU:0"]
    assert [n.split("(")[0] for n, _, _ in modules] == ["jit_window_fn"] * 2


def test_enclosing_operations_are_dropped(trace):
    every = trace.device_ops()["/device:TPU:0"]
    leaves = trace.device_ops(leaves=True)["/device:TPU:0"]
    whiles = [e for e in every if e[0].startswith("%while")]
    assert len(whiles) == 2  # one scan over the five steps of each window
    assert not [e for e in leaves if e[0].startswith("%while")]
    assert len(leaves) == len(every) - 2
    # the leaves fill the loops that enclosed them, and no more
    assert sum(d for _, _, d in leaves) <= sum(d for _, _, d in whiles) * 1.0001
    assert tr.union_seconds(leaves) == pytest.approx(
        sum(d for _, _, d in whiles), rel=2e-3)


def test_busy_and_idle(trace, ctx):
    lo, hi = trace.span()
    busy = tr.union_seconds(trace.device_ops()["/device:TPU:0"])
    assert hi - lo == pytest.approx(3.099897343, rel=1e-6)
    assert busy == pytest.approx(3.096134437, rel=1e-6)
    idle = trace_idle_share.read(ctx[0])
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    assert idle == pytest.approx(0.1214, abs=1e-4)
    gaps = tr.gaps(trace.device_ops()["/device:TPU:0"])
    assert sum(d for _, d in gaps) == pytest.approx(hi - lo - busy, rel=1e-6)


def test_kernel_time_and_roofline_by_hand(trace, ctx):
    c, cell = ctx
    args = next(m for m in cell["per_layer"]
                if m["name"] == "attn_roofline.train")["args"]
    hits = trace_time_by_name.matched(c, args["events"])["/device:TPU:0"]
    fwd = trace_time_by_name.matched(c, args["unit_events"])["/device:TPU:0"]
    # 8 layers x 10 steps, a forward and a backward kernel each
    assert len(hits) == 160 and len(fwd) == 80
    assert all(tr.CUSTOM_CALL in n for n, _, _ in hits)
    seconds = sum(d for _, _, d in hits)
    assert seconds == pytest.approx(0.07513 + 0.15296, rel=1e-3)
    # one layer's attention, forward + backward, 12 rows of 1024 at D=2048
    flops = 3 * (2 * 2 * 1024 * 1024 * 2048 / 2) * 12
    by_hand = 100 * (80 * flops / 197e12) / seconds
    got = roofline.read(c, **run._resolve(args, cell, c))
    assert got == pytest.approx(by_hand, rel=1e-9)
    assert got == pytest.approx(27.5275, abs=1e-3)
    assert got < 100


def test_time_by_name_per_event(ctx):
    c, _ = ctx
    ms = trace_time_by_name.read(c, events="window_fn", line="^XLA Modules$",
                                 per="event", per_scale=5)
    assert ms == pytest.approx(1e3 * 3.096137 / 2 / 5, rel=1e-5)  # a step
    assert trace_time_by_name.read(c, events="no_such_kernel") is None
    assert roofline.read(c, "no_such_kernel", "no_such_kernel",
                         "attn_train_flops_per_layer",
                         {"batch": 12, "seq_len": 1024}, "compute") is None


def test_breakdown_is_small_and_labelled(trace):
    b = tr.breakdown(trace, spec.kind("train").ANNOTATIONS)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert not [n for n, _ in b["device_ops"] if n.startswith("%while")]
    assert b["idle_gaps"][0][0] == "train_window.harvest"
    json.dumps(b)
    with pytest.raises(ops.UnknownDevice):
        roofline.read({**{"trace": trace, "sizes": {"n_embd": 2048},
                          "device_kind": "cpu"}},
                      "fused_attention", "fused_attention",
                      "attn_train_flops_per_layer",
                      {"batch": 12, "seq_len": 1024}, "compute")


def test_the_profilers_own_calls_are_left_out():
    t = tr.Tracer(False, "", 0, 0)
    assert t.stalled(0.0, 30.0) == 0.0  # an untraced run has none
    t.stalls = [(9.0, 10.5), (13.5, 15.5)]
    assert t.stalled(0.0, 30.0) == pytest.approx(3.5)
    assert t.stalled(10.0, 14.0) == pytest.approx(1.0)
    assert t.stalled(10.5, 13.5) == 0.0  # a latency between the two counts

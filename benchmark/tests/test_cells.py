"""Each kind of cell end to end at a tiny width under ``--rehearsal``, and the
program against the plain reference there."""

import pytest

from benchmark.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,metric", [("tiny-train", "step_ms_p50.train"),
                                         ("tiny-serve", "slot_occupancy.serve")])
def test_cell_runs_and_is_correct(copy, cell, metric):
    _, plain, _ = tiny.run(copy, cell, trace=0, seconds=2.0)
    _, traced, _ = tiny.run(copy, cell, trace=1, seconds=2.0, seed=2**31 + 11)
    for res in (plain, traced):
        assert KEYS <= set(res)
        assert res["correct"] is True and res["failed"] == 0
        assert res["attempted"] > 0
        assert res["device"]["platform"] == "cpu"
        assert list(res)[-1] == "compared"
        for name, c in res["compared"].items():
            assert c["value"] <= c["limit"], name
    # a rehearsal names the CPU and carries no device metric
    assert set(plain["metrics"]) == {"setup_s"}
    assert set(traced["metrics"]) == {metric}
    assert "busy_s" not in traced["device"]


def test_no_accelerator_no_result(copy):
    rc, res, err = tiny.run(copy, "tiny-train", rehearsal=False, check=False)
    assert rc == 3 and res is None
    assert "no TPU" in err

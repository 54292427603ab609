"""Metrics utilities: wandb run-id persistence across resume (parity:
/root/reference/launch.py:60-67) and MFU arithmetic."""

import types

from midgpt_tpu.utils.metrics import _load_or_create_wandb_id, flops_per_token


def _fake_wandb(ids):
    it = iter(ids)
    return types.SimpleNamespace(
        util=types.SimpleNamespace(generate_id=lambda: next(it))
    )


def test_wandb_id_persisted_and_reused(tmp_path):
    rundir = str(tmp_path / "run")
    first = _load_or_create_wandb_id(rundir, _fake_wandb(["abc123", "XXX"]))
    assert first == "abc123"
    # a "resumed" process must get the stored id, not a fresh one
    second = _load_or_create_wandb_id(rundir, _fake_wandb(["YYY"]))
    assert second == "abc123"
    assert (tmp_path / "run" / "wandb_id.txt").read_text().strip() == "abc123"


def test_wandb_id_empty_rundir_is_none():
    assert _load_or_create_wandb_id("", _fake_wandb(["a"])) is None


def test_flops_per_token_gpt2_small():
    from midgpt_tpu.config import get_config

    model = get_config("openwebtext").model
    # 6 * (param matmuls) + causal attention term; ~798 MFLOP/token for
    # the 124M config (sanity: within 10% of 6 * 130M)
    f = flops_per_token(model)
    assert 7.0e8 < f < 9.0e8


def test_device_peak_flops_has_no_default():
    """A device that is not in the peak table is an error, not a v5e: a
    CPU run must not be able to print a chip-relative utilization."""
    import types

    import jax
    import pytest

    from midgpt_tpu.utils.metrics import (
        UnknownDevicePeak,
        device_peak_flops,
        mfu,
    )
    from midgpt_tpu.config import get_config

    with pytest.raises(UnknownDevicePeak):
        device_peak_flops()  # this suite runs on the CPU
    with pytest.raises(UnknownDevicePeak):
        mfu(1e5, get_config("openwebtext").model, jax.device_count())
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9x")
    with pytest.raises(UnknownDevicePeak):
        device_peak_flops(unknown)
    # a CPU whose kind string happens to contain a table key is still a CPU
    odd = types.SimpleNamespace(platform="cpu", device_kind="v5 emulator")
    with pytest.raises(UnknownDevicePeak):
        device_peak_flops(odd)

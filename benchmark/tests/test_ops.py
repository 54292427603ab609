"""The operation and byte counts against hand arithmetic, at both widths."""

import json
import os

import pytest

from benchmark import ops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def sizes(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_parameter_counts_by_hand():
    xl = sizes("midgpt-xl")
    per_layer = 2048 * 6144 + 2048 * 2048 + 2 * 2048 * 8192  # 50.3 M
    assert per_layer == 50_331_648
    assert ops.matmul_params(xl) == 24 * per_layer + 2048 * 50304
    assert ops.total_params(xl) == (
        24 * per_layer + 2 * 2048 * 50304 + 24 * 2 * 128)
    assert round(ops.total_params(xl) / 1e9, 3) == 1.414
    l8 = sizes("midgpt-xl-l8")
    assert round(ops.total_params(l8) / 1e6) == 609
    sm = sizes("midgpt-124m")
    per_layer = 768 * 2304 + 768 * 768 + 2 * 768 * 3072  # 7.08 M
    assert ops.matmul_params(sm) == 12 * per_layer + 768 * 50304
    assert round((ops.total_params(sm) - 768 * 50304) / 1e6) == 124


@pytest.mark.parametrize("name,gflop", [("midgpt-xl-l8", 3.13),
                                        ("midgpt-124m", 0.80)])
def test_train_flops_per_token(name, gflop):
    s = sizes(name)
    by_hand = 6 * ops.matmul_params(s) + (
        6 * 2 * s["n_layer"] * s["n_embd"] * 1024 / 2)
    assert ops.train_flops_per_token(s) == by_hand
    assert round(by_hand / 1e9, 2) == gflop


def test_attention_flops_of_one_layer():
    # QK^T and PV forward: 2 * 2 * T * T * D, halved by the causal mask;
    # backward twice that. 12 rows of 1024 at D = 2048:
    s = sizes("midgpt-xl-l8")
    fwd = 2 * 2 * 1024 * 1024 * 2048 / 2
    assert ops.attn_train_flops_per_layer(s, 12, 1024) == 3 * fwd * 12
    s = sizes("midgpt-124m")
    assert ops.attn_train_flops_per_layer(s, 24, 1024) == (
        3 * 2 * 2 * 1024 * 1024 * 768 / 2 * 24)


def test_kv_bytes():
    xl = sizes("midgpt-xl")
    # K and V of a position in one layer: 2 x 2048 bf16; over 24 layers the
    # 196,608 bytes a token of PR 21's finding 2
    assert ops.kv_read_bytes_per_layer(xl, 1000.0) == 1000 * 2 * 2048 * 2
    assert 24 * ops.kv_read_bytes_per_layer(xl, 1.0) == 196_608


def test_forward_flops_of_sequence():
    xl = sizes("midgpt-xl")
    one = ops.forward_flops_per_token(xl, 100.0)
    assert one == 2 * ops.matmul_params(xl) + 4 * 24 * 2048 * 100.0
    # positions 0 .. 9 see 1 .. 10 keys: mean 5.5
    assert ops.forward_flops_of_sequence(xl, 0, 10) == pytest.approx(
        10 * ops.forward_flops_per_token(xl, 5.5))


def test_peaks_have_no_default():
    assert ops.peak("TPU v5 lite", "flops") == 197e12
    assert ops.peak("TPU v5 lite", "bytes_per_s") == 819e9
    with pytest.raises(ops.UnknownDevice):
        ops.peak("cpu", "flops")

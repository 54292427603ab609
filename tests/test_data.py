"""Data pipeline tests: determinism, checkpointable state, target shift,
process sharding."""

import numpy as np
import pytest

from midgpt_tpu.data import Loader, load_shard, sample_batch, write_tokens


@pytest.fixture
def token_file(tmp_path):
    path = str(tmp_path / "train.bin")
    write_tokens(path, np.arange(10_000) % 256)
    return path


def test_load_shard_full(token_file):
    shard = load_shard(token_file)
    assert len(shard.tokens) == 10_000
    assert shard.tokens.dtype == np.uint16


def test_load_shard_per_process(token_file):
    s0 = load_shard(token_file, 0, 4)
    s3 = load_shard(token_file, 3, 4)
    assert len(s0.tokens) == len(s3.tokens) == 2500
    assert s0.tokens[0] == 0
    assert s3.offset == 7500


def test_sample_batch_shift_and_shape(token_file):
    shard = load_shard(token_file)
    x, y = sample_batch(shard, 32, (2, 4), seed=1, step=0)
    assert x.shape == y.shape == (2, 4, 32)
    assert x.dtype == np.int32
    # y is x shifted by one
    np.testing.assert_array_equal(x[..., 1:], y[..., :-1])


def test_sample_batch_deterministic(token_file):
    shard = load_shard(token_file)
    x1, _ = sample_batch(shard, 32, (2, 4), seed=1, step=7)
    x2, _ = sample_batch(shard, 32, (2, 4), seed=1, step=7)
    np.testing.assert_array_equal(x1, x2)
    x3, _ = sample_batch(shard, 32, (2, 4), seed=1, step=8)
    assert not np.array_equal(x1, x3)
    x4, _ = sample_batch(shard, 32, (2, 4), seed=2, step=7)
    assert not np.array_equal(x1, x4)


def test_loader_resume_reproduces_sequence(token_file):
    """The key fix over the reference (SURVEY.md 2.3): resume-exact data
    order."""
    shard = load_shard(token_file)
    a = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=5)
    seq_a = [a.next()[0] for _ in range(6)]

    b = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=5)
    b.next(); b.next(); b.next()
    state = b.state_dict()

    c = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=5)
    c.load_state_dict(state)
    for i in range(3, 6):
        np.testing.assert_array_equal(c.next()[0], seq_a[i])


def test_loader_seed_mismatch_rejected(token_file):
    shard = load_shard(token_file)
    a = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=5)
    with pytest.raises(AssertionError):
        a.load_state_dict({"step": 3, "seed": 6})


def test_streams_are_independent(token_file):
    shard = load_shard(token_file)
    x1, _ = sample_batch(shard, 32, (4,), seed=1, step=0, stream=0)
    x2, _ = sample_batch(shard, 32, (4,), seed=1, step=0, stream=1)
    assert not np.array_equal(x1, x2)


def test_native_gather_matches_numpy(token_file):
    """The C++ gather (midgpt_tpu/native/gather.cpp) must be bit-identical
    to the numpy recipe (parity: reference train.py:61-62)."""
    from midgpt_tpu import native

    shard = load_shard(token_file)
    offsets = np.array([0, 17, 500, 9900 - 33], dtype=np.int64)
    xs, ys = native.gather_windows(shard.tokens, offsets, 32)
    # numpy oracle
    idx = offsets[:, None] + np.arange(33)[None, :]
    windows = np.take(shard.tokens, idx, axis=0).astype(np.int32)
    np.testing.assert_array_equal(xs, windows[:, :-1])
    np.testing.assert_array_equal(ys, windows[:, 1:])


def test_native_gather_bounds_check(token_file):
    from midgpt_tpu import native

    shard = load_shard(token_file)
    with pytest.raises(IndexError):
        native.gather_windows(
            shard.tokens, np.array([10_000 - 8], dtype=np.int64), 32
        )
    with pytest.raises(IndexError):
        native.gather_windows(shard.tokens, np.array([-1], dtype=np.int64), 32)


def test_native_library_builds():
    """The toolchain is baked into the image, so the native path (not the
    fallback) must be what tests exercise — built here, from the
    committed source, under a name that says so."""
    import hashlib
    import os

    from midgpt_tpu import native

    assert native.native_available()
    assert native.gather_backend() == "native"
    with open(native._SRC, "rb") as f:
        src = f.read()
    want = hashlib.sha256(src + " ".join(native._FLAGS).encode())
    assert os.path.basename(native._lib_path()) == (
        f"libdatagather-{want.hexdigest()[:16]}.so"
    )
    assert os.path.exists(native._lib_path())
    assert "-march=native" not in native._FLAGS


def test_native_build_failure_is_said_not_hidden(monkeypatch, capsys):
    """No toolchain: the numpy path serves the same windows, and the
    reason is on stderr and in gather_backend() — not a silent switch."""
    from midgpt_tpu import native

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_why_numpy", None)
    monkeypatch.setattr(
        native, "_lib_path", lambda: "/nonexistent-dir/libdatagather-x.so"
    )
    assert not native.native_available()
    assert native.gather_backend().startswith("numpy (")
    assert "falls back to numpy" in capsys.readouterr().err
    tokens = np.arange(100, dtype=np.uint16)
    x, y = native.gather_windows(tokens, np.array([3]), 8)
    np.testing.assert_array_equal(x[0], np.arange(3, 11))
    np.testing.assert_array_equal(y[0], np.arange(4, 12))


def test_prefetch_loader_matches_sync(token_file):
    from midgpt_tpu.data import PrefetchLoader

    shard = load_shard(token_file)
    sync = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    expected = [sync.next() for _ in range(8)]

    pre = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    )
    try:
        for i in range(8):
            x, y = pre.next()
            np.testing.assert_array_equal(x, expected[i][0])
            np.testing.assert_array_equal(y, expected[i][1])
    finally:
        pre.stop()


def test_prefetch_window_stacks_consecutive_batches(token_file):
    """Window mode: each next() is K consecutive loader batches stacked
    along a new leading axis — the [K, ...] window the fused multi-step
    dispatch consumes."""
    from midgpt_tpu.data import PrefetchLoader

    shard = load_shard(token_file)
    sync = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    expected = [sync.next() for _ in range(6)]

    pre = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9),
        window=3,
    )
    try:
        for w in range(2):
            x, y = pre.next()
            assert x.shape == (3, 2, 16)
            for i in range(3):
                np.testing.assert_array_equal(x[i], expected[3 * w + i][0])
                np.testing.assert_array_equal(y[i], expected[3 * w + i][1])
    finally:
        pre.stop()


def test_prefetch_window_plan_partial_first_and_last(token_file):
    """An explicit window_plan (the trainer's dispatch plan after an
    off-grid resume) yields per-item stacks of the planned sizes, then the
    worker stops — no draws past the plan."""
    from midgpt_tpu.data import PrefetchLoader

    shard = load_shard(token_file)
    sync = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    expected = [sync.next() for _ in range(6)]

    pre = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9),
        window=3, window_plan=[2, 3, 1],
    ).start()
    try:
        seen = 0
        for w in [2, 3, 1]:
            x, _ = pre.next()
            assert x.shape == (w, 2, 16)
            for i in range(w):
                np.testing.assert_array_equal(x[i], expected[seen + i][0])
            seen += w
        assert pre.state_dict()["step"] == 6
        # past the plan: the worker published a terminal sentinel — one
        # more next() must RAISE, not block forever on an empty queue
        with pytest.raises(RuntimeError, match="window_plan exhausted"):
            pre.next()
    finally:
        pre.stop()


def test_prefetch_window_state_replays_unconsumed_mid_window(token_file):
    """Stop/resume mid-window (depth-aware): batches drawn into queued-but-
    unconsumed windows must NOT count as consumed — a resume from
    state_dict() replays every batch of every unconsumed window exactly
    (extends the generation-zombie tests above to window mode)."""
    import time

    from midgpt_tpu.data import PrefetchLoader

    shard = load_shard(token_file)
    pre = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9),
        depth=3, window=2,
    ).start()
    try:
        consumed = [pre.next() for _ in range(2)]  # 2 windows = 4 batches
        time.sleep(0.2)  # let the worker queue more windows
        state = pre.state_dict()
        # only the consumed windows' batches count (2 windows x 2)
        assert state["step"] == 4
    finally:
        pre.stop()

    sync = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    expected = [sync.next() for _ in range(6)]
    resumed = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9),
        window=2,
    )
    resumed.load_state_dict(state)
    try:
        x, _ = resumed.next()  # replays batches 4 and 5 exactly
        np.testing.assert_array_equal(x[0], expected[4][0])
        np.testing.assert_array_equal(x[1], expected[5][0])
    finally:
        resumed.stop()
    del consumed


def test_prefetch_loader_state_excludes_unconsumed(token_file):
    """Checkpointed loader state must count only consumed batches, not ones
    sitting in the prefetch queue."""
    import time

    from midgpt_tpu.data import PrefetchLoader

    shard = load_shard(token_file)
    pre = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9), depth=3
    ).start()
    try:
        consumed = [pre.next() for _ in range(2)]
        time.sleep(0.2)  # let the worker fill the queue
        state = pre.state_dict()
        assert state["step"] == 2
    finally:
        pre.stop()

    # resume from the state replays batch #2 next
    sync = Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    expected = [sync.next() for _ in range(3)]
    resumed = PrefetchLoader(
        Loader(shard=shard, block_size=16, batch_shape=(2,), seed=9)
    )
    resumed.load_state_dict(state)
    try:
        np.testing.assert_array_equal(resumed.next()[0], expected[2][0])
    finally:
        resumed.stop()
    del consumed

"""Token-stream data pipeline.

Capability parity with the reference's loader (/root/reference/src/train.py:56-66
``get_batch`` + :122-125 per-process splitting), redesigned:

- **Deterministic + checkpointable**: the reference draws offsets from
  unseeded numpy (train.py:60), so resume changes the data order (SURVEY.md
  2.3). Here every batch is a pure function of (seed, step, process_index)
  via a counter-based Philox generator — the loader "state" checkpointed is
  just the step number, and resume is exact.
- Same throughput recipe: memmapped uint16 token file, windows gathered by
  the native multi-threaded C++ gather (midgpt_tpu.native, numpy fallback),
  targets = inputs shifted by one.
- Per-process contiguous shards (equal-size, unlike the reference's
  ``int(n/p)+1`` imbalance).
- ``PrefetchLoader`` overlaps next-batch assembly (gather + host->device
  transfer) with the device step on a background thread.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import typing as tp

import numpy as np

from midgpt_tpu.native import gather_windows
from midgpt_tpu.telemetry import span


@dataclasses.dataclass(frozen=True)
class Shard:
    """A process-local contiguous view of the global token stream."""

    tokens: np.ndarray  # 1-D uint16 view (memmap-backed)
    global_len: int
    offset: int  # start of this shard in the global stream


def load_shard(
    path: str,
    process_index: int = 0,
    process_count: int = 1,
    in_memory: bool = True,
) -> Shard:
    """Memmap ``path`` and take this process's contiguous 1/process_count
    slice (parity: train.py:132-136, split_array_by_idx train.py:122-124)."""
    data = np.memmap(path, dtype=np.uint16, mode="r")
    n = len(data)
    per = n // process_count
    lo, hi = process_index * per, (process_index + 1) * per
    shard = data[lo:hi]
    if in_memory:
        shard = np.asarray(shard)  # host-RAM copy (reference .copy())
    return Shard(tokens=shard, global_len=n, offset=lo)


def _rng(seed: int, step: int, process_index: int, stream: int) -> np.random.Generator:
    """Counter-based generator: unique, reproducible per (seed, step, proc)."""
    return np.random.Generator(
        np.random.Philox(key=seed, counter=[0, stream, step, process_index])
    )


def sample_batch(
    shard: Shard,
    block_size: int,
    batch_shape: tp.Tuple[int, ...],
    seed: int,
    step: int,
    process_index: int = 0,
    stream: int = 0,
) -> tp.Tuple[np.ndarray, np.ndarray]:
    """Random block_size windows, with replacement.

    Returns (x, y) int32 arrays shaped ``batch_shape + (block_size,)``;
    y is x shifted by one (parity: train.py:56-66, incl. the
    ``(g_accum, B, T)`` reshape for microbatching).
    """
    n_seqs = int(np.prod(batch_shape))
    rng = _rng(seed, step, process_index, stream)
    offsets = rng.integers(
        0, len(shard.tokens) - block_size - 1, size=(n_seqs,)
    )
    x, y = gather_windows(shard.tokens, offsets, block_size)
    return (
        x.reshape(*batch_shape, block_size),
        y.reshape(*batch_shape, block_size),
    )


@dataclasses.dataclass
class Loader:
    """Stateful wrapper holding the (tiny) loader state = current step.

    ``state_dict``/``load_state_dict`` round-trip through checkpoints;
    restoring the step reproduces the exact batch sequence.
    """

    shard: Shard
    block_size: int
    batch_shape: tp.Tuple[int, ...]  # e.g. (g_accum, local_batch)
    seed: int
    process_index: int = 0
    step: int = 0
    stream: int = 0

    def next(self) -> tp.Tuple[np.ndarray, np.ndarray]:
        x, y = sample_batch(
            self.shard,
            self.block_size,
            self.batch_shape,
            self.seed,
            self.step,
            self.process_index,
            self.stream,
        )
        self.step += 1
        return x, y

    def peek(self, step: int) -> tp.Tuple[np.ndarray, np.ndarray]:
        return sample_batch(
            self.shard,
            self.block_size,
            self.batch_shape,
            self.seed,
            step,
            self.process_index,
            self.stream,
        )

    def state_dict(self) -> tp.Dict[str, int]:
        return {"step": self.step, "seed": self.seed}

    def load_state_dict(self, state: tp.Mapping[str, int]) -> None:
        assert int(state["seed"]) == self.seed, (
            f"loader seed changed: ckpt {state['seed']} vs config {self.seed}"
        )
        self.step = int(state["step"])


class _PrefetchError:
    """Wraps an exception raised on the prefetch thread for re-raising on
    the consumer thread (a bare daemon-thread death would hang next())."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _PlanExhausted:
    """Terminal sentinel enqueued when a finite window_plan runs out, so a
    next() past the plan raises instead of blocking forever on an empty
    queue (the worker has exited)."""


class PrefetchLoader:
    """Background-thread prefetch around a Loader: the next batch is
    gathered (and optionally pushed to device) while the current train step
    runs. The reference assembles every batch synchronously between steps
    (train.py:203-207); overlapping it removes that host time from the
    step critical path.

    ``transform`` (e.g. a make_global_array closure) runs on the prefetch
    thread — jax.device_put / make_array_from_process_local_data are
    thread-safe for this producer/consumer pattern.

    **Window mode** (``window > 1`` or an explicit ``window_plan``): each
    produced item stacks W consecutive batches along a new leading axis —
    ``[W, *batch_shape, T]`` — feeding the fused multi-step dispatch
    (train.make_train_window) one K-deep batch window per launch.
    ``window_plan`` is the trainer's finite per-item size schedule (a
    short first window re-aligns an off-grid resume; a short last window
    covers ``max_steps % K``); the worker stops when the plan runs out.
    Consumption is accounted in LOADER STEPS: ``state_dict`` counts only
    the batches of consumed windows, so stop/resume mid-window replays
    every unconsumed window batch exactly.

    Checkpointing goes through the wrapped loader's state_dict; the
    prefetch queue is drained on load so resumed batches are exact.
    """

    def __init__(
        self,
        loader: Loader,
        transform: tp.Optional[tp.Callable] = None,
        depth: int = 2,
        window: int = 1,
        window_plan: tp.Optional[tp.Sequence[int]] = None,
    ):
        assert window >= 1, window
        self.loader = loader
        self._transform = transform if transform is not None else lambda *b: b
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread: tp.Optional[threading.Thread] = None
        self._window = window
        self._plan = tuple(window_plan) if window_plan is not None else None
        self._windowed = window > 1 or self._plan is not None
        # consumption is tracked here, not via loader.step: the worker may
        # have drawn batches that no one has consumed yet. In window mode
        # _consumed counts loader STEPS (batches), _consumed_items counts
        # windows (the plan cursor for a restarted worker generation).
        self._start_step = loader.step
        self._consumed = 0
        self._consumed_items = 0

    def _item_sizes(self, from_item: int) -> tp.Iterator[int]:
        """Window sizes the worker should produce, starting at item index
        ``from_item``: the remaining plan suffix, or an unbounded stream
        of ``window``-sized items when no plan was given."""
        if self._plan is not None:
            yield from self._plan[from_item:]
            return
        while True:
            yield self._window

    def _worker(
        self, stop: threading.Event, q: "queue.Queue", begin_step: int,
        from_item: int,
    ) -> None:
        # draws via the PURE loader.peek with a generation-local counter —
        # the shared Loader is never mutated, so a join-timeout zombie
        # cannot corrupt another generation's (or a resume's) data order
        produced = 0
        for w in self._item_sizes(from_item):
            if stop.is_set():
                return
            try:
                with span("midgpt.loader.produce", k=w):
                    with span("midgpt.loader.gather"):
                        if self._windowed:
                            draws = [
                                self.loader.peek(begin_step + produced + i)
                                for i in range(w)
                            ]
                            cols = [np.stack(col) for col in zip(*draws)]
                        else:
                            cols = self.loader.peek(begin_step + produced)
                    with span("midgpt.loader.transfer"):
                        batch = self._transform(*cols)
                produced += w
                item = (w, batch)
            except BaseException as exc:  # propagate to the consumer
                item = (w, _PrefetchError(exc))
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if isinstance(item[1], _PrefetchError):
                return
        # finite plan exhausted (only a bounded _item_sizes ends the loop):
        # publish a terminal sentinel so a consumer calling next() past the
        # plan raises instead of blocking forever on an empty queue
        sentinel = (0, _PlanExhausted())
        while not stop.is_set():
            try:
                q.put(sentinel, timeout=0.1)
                break
            except queue.Full:
                continue

    def start(self) -> "PrefetchLoader":
        if self._thread is None:
            # each worker generation gets its own stop event + queue so a
            # join-timeout zombie from a previous generation can never feed
            # the current one
            self._thread = threading.Thread(
                target=self._worker,
                args=(
                    self._stop, self._queue,
                    self._start_step + self._consumed, self._consumed_items,
                ),
                daemon=True,
            )
            self._thread.start()
        return self

    def next(self, log=None, step: int = 0):
        """The next item; blocks while the worker has none ready. A
        consumer that traces passes its ``TelemetryLog`` and the step the
        item is for: the wait is then also its ``prefetch_wait`` record."""
        if self._thread is None:
            self.start()
        with span("midgpt.loader.wait", log, "prefetch_wait", step=step):
            w, batch = self._queue.get()
        if isinstance(batch, _PrefetchError):
            self.stop()
            raise batch.exc
        if isinstance(batch, _PlanExhausted):
            self.stop()
            raise RuntimeError(
                f"PrefetchLoader window_plan exhausted after "
                f"{self._consumed_items} windows ({self._consumed} batches): "
                "next() was called more times than the plan has items"
            )
        self._consumed += w
        self._consumed_items += 1
        return batch

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            while True:  # unblock a producer stuck on a full queue
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            # A worker stuck >5s mid-transform stays alive, but it holds
            # THIS generation's stop event (already set) + queue and only
            # ever calls the pure loader.peek, so it can neither feed a
            # later generation nor corrupt shared state.
            self._thread.join(timeout=5)
            self._thread = None
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._queue.maxsize)

    def state_dict(self) -> tp.Dict[str, int]:
        # batches sitting in the queue (or in-flight on the worker) were
        # drawn but never consumed — resume replays from the consumed count
        return {
            "step": self._start_step + self._consumed,
            "seed": self.loader.seed,
        }

    def load_state_dict(self, state: tp.Mapping[str, int]) -> None:
        # note for window mode: a restored step generally needs a NEW
        # window plan (the trainer recomputes it from the restored step and
        # constructs a fresh PrefetchLoader); loading here restarts any
        # existing plan from its first entry
        self.stop()
        self.loader.load_state_dict(state)
        self._start_step = self.loader.step
        self._consumed = 0
        self._consumed_items = 0


def write_tokens(path: str, tokens: np.ndarray) -> None:
    """Write a uint16 token stream the way the prep scripts do
    (parity: data/shakespeare_char/prepare.py:54-61 .tofile)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.asarray(tokens, dtype=np.uint16).tofile(path)

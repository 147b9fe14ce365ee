"""Data pipeline: token-bin datasets, deterministic window sampling,
threaded host→device prefetch.

The reference feeds C4/WikiText-2 through a C++ dataset/loader
(BASELINE.json; reference checkout never mounted — SURVEY.md §0). Here the
on-disk format is a flat binary of token ids (uint16/uint32) with a JSON
sidecar (``<name>.meta.json``: {"dtype", "count", "vocab_size"}), mmap'd on
the host. Sampling is a pure function of (seed, step) — resuming at step N
reproduces the exact batch sequence with no iterator state to checkpoint.
A background thread overlaps host batch assembly + ``jax.device_put`` with
the device step. ``orion_tpu/runtime/`` provides the C++ fast path for
assembly; this module is the always-available fallback with the same
format.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from typing import Iterator, Optional

import jax
import numpy as np

from orion_tpu.obs.trace import note_open
from orion_tpu.resilience.inject import fire
from orion_tpu.resilience.retry import RetryPolicy, call_with_retries
from orion_tpu.resilience.watchdog import StallError

Array = jax.Array

_SM64_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_SM64_M1 = np.uint64(0xBF58476D1CE4E5B9)
_SM64_M2 = np.uint64(0x94D049BB133111EB)
_STEP_MIX = np.uint64(0xD1B54A32D192ED03)
_ROW_MIX = np.uint64(0x8CB92BA72F3D8DD7)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer (the canonical sampler hash, mirrored
    bit-for-bit by runtime/loader.cc)."""
    with np.errstate(over="ignore"):
        z = x + _SM64_GAMMA
        z = (z ^ (z >> np.uint64(30))) * _SM64_M1
        z = (z ^ (z >> np.uint64(27))) * _SM64_M2
        return z ^ (z >> np.uint64(31))


def window_starts(seed: int, step: int, batch_size: int, n_windows: int) -> np.ndarray:
    """Deterministic window start offsets for (seed, step)."""
    rows = np.arange(batch_size, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = (
            np.uint64(seed)
            ^ (np.uint64(step) * _STEP_MIX)
            ^ (rows * _ROW_MIX)
        )
    return (_splitmix64(x) % np.uint64(n_windows)).astype(np.int64)


def write_token_bin(path: str, tokens: np.ndarray, vocab_size: int) -> None:
    """Write the token-bin format (+ sidecar)."""
    dtype = np.uint16 if vocab_size <= 65536 else np.uint32
    arr = np.asarray(tokens, dtype=dtype)
    arr.tofile(path)
    # atomic publish (write-tmp-then-replace): a preempted writer must not
    # leave a torn sidecar that silently mis-dtypes every later run
    from orion_tpu.training.checkpoint import atomic_write_json

    atomic_write_json(
        path + ".meta.json",
        {"dtype": str(dtype.__name__ if hasattr(dtype, '__name__') else np.dtype(dtype).name),
         "count": int(arr.size), "vocab_size": int(vocab_size)},
    )


class TokenBinDataset:
    """mmap'd flat token file; windows of seq_len+1 sampled deterministically."""

    def __init__(self, path: str, seq_len: int):
        meta_path = path + ".meta.json"
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            dtype = np.dtype(meta["dtype"])
            self.vocab_size = int(meta.get("vocab_size", np.iinfo(dtype).max + 1))
        else:
            dtype = np.dtype(np.uint16)
            self.vocab_size = 65536
        self.tokens = np.memmap(path, dtype=dtype, mode="r")
        self.seq_len = seq_len
        self.n_windows = len(self.tokens) - seq_len - 1
        assert self.n_windows > 0, f"{path}: too few tokens for seq_len={seq_len}"

    def batch(self, seed: int, step: int, batch_size: int) -> np.ndarray:
        """[B, seq_len+1] int32; pure function of (seed, step).

        Window starts come from ``window_starts`` (splitmix64) — the exact
        same integer stream the C++ loader (runtime/loader.cc) computes, so
        the fallback and the native path are batch-for-batch identical."""
        starts = window_starts(seed, step, batch_size, self.n_windows)
        return self.gather(starts)

    def gather(self, starts: np.ndarray) -> np.ndarray:
        """[len(starts), seq_len+1] int32 windows at explicit offsets (the
        sharded-dataset building block; native twin in runtime)."""
        out = np.empty((len(starts), self.seq_len + 1), dtype=np.int32)
        for i, s in enumerate(starts):
            out[i] = self.tokens[s : s + self.seq_len + 1]
        return out


class ShardedTokenBinDataset:
    """Many token-bin shards as ONE virtual corpus (VERDICT r4 #2: a
    pretraining-scale corpus needn't be one file). The window space is the
    concatenation of each shard's windows — a global start from
    ``window_starts`` maps to (shard, local offset) by prefix-sum binary
    search, so windows never span shard boundaries and the (seed, step) ->
    batch contract is exactly the single-file one with ``n_windows =
    sum_i n_windows_i``. Per-shard gathers ride the C++ loader's
    explicit-starts entry (runtime/loader.cc::orion_loader_gather) when
    the .so is built, the mmap fallback otherwise."""

    def __init__(self, paths, seq_len: int):
        assert paths, "ShardedTokenBinDataset needs at least one shard"
        from orion_tpu import runtime

        self.paths = list(paths)
        self.seq_len = seq_len
        # gate on the GATHER entry, not just native_available(): a stale
        # pre-r5 .so loads fine but lacks orion_loader_gather, and the
        # promised mmap fallback must engage instead of crashing at the
        # first batch (r5 review)
        lib = runtime._load() if runtime.native_available() else None
        if lib is not None and hasattr(lib, "orion_loader_gather"):
            self.shards = [
                runtime.NativeTokenBinDataset(p, seq_len) for p in self.paths
            ]
        else:
            self.shards = [TokenBinDataset(p, seq_len) for p in self.paths]
        vocabs = {s.vocab_size for s in self.shards}
        assert len(vocabs) == 1, (
            f"shards disagree on vocab_size: { {p: s.vocab_size for p, s in zip(self.paths, self.shards)} }"
        )
        self.vocab_size = vocabs.pop()
        per = np.asarray([s.n_windows for s in self.shards], dtype=np.int64)
        assert (per > 0).all(), "every shard must hold > seq_len+1 tokens"
        self._cum = np.cumsum(per)
        self.n_windows = int(self._cum[-1])
        self.n_tokens = int(sum(
            getattr(s, "n_tokens", s.n_windows + seq_len + 1)
            for s in self.shards
        ))

    def batch(self, seed: int, step: int, batch_size: int) -> np.ndarray:
        starts = window_starts(seed, step, batch_size, self.n_windows)
        which = np.searchsorted(self._cum, starts, side="right")
        local = starts - np.concatenate([[0], self._cum[:-1]])[which]
        out = np.empty((batch_size, self.seq_len + 1), dtype=np.int32)
        for si in np.unique(which):
            rows = np.nonzero(which == si)[0]
            out[rows] = self.shards[si].gather(local[rows])
        return out

    def close(self):
        for s in self.shards:
            if hasattr(s, "close"):
                s.close()


class SyntheticDataset:
    """Deterministic pseudo-data with learnable structure (each token is a
    fixed function of the previous two) so overfit/convergence tests have
    signal; same ``batch(seed, step, b)`` interface as TokenBinDataset."""

    def __init__(self, vocab_size: int, seq_len: int):
        self.vocab_size = vocab_size
        self.seq_len = seq_len

    def batch(self, seed: int, step: int, batch_size: int) -> np.ndarray:
        rng = np.random.Generator(np.random.Philox(key=[seed, step]))
        t = self.seq_len + 1
        out = np.empty((batch_size, t), dtype=np.int32)
        out[:, 0] = rng.integers(0, self.vocab_size, size=batch_size)
        out[:, 1] = rng.integers(0, self.vocab_size, size=batch_size)
        for j in range(2, t):
            out[:, j] = (out[:, j - 1] * 31 + out[:, j - 2] * 7 + 3) % self.vocab_size
        return out


class DataLoader:
    """Background-thread prefetch: dataset.batch → device_put with the batch
    sharding, ``prefetch`` batches deep. Restart-safe: construction takes the
    starting step, and batches are pure functions of (seed, step).

    Resilience: transient ``OSError`` from the dataset read retries with
    jittered backoff (``retry``); a worker that dies anyway re-raises its
    ORIGINAL exception (traceback intact, as ``__cause__``) from
    ``__next__``; and with ``stall_timeout`` set, a consumer that waits
    longer than that for a batch gets a diagnosable
    :class:`~orion_tpu.resilience.watchdog.StallError` instead of blocking
    forever on a hung read (dead NFS mount, wedged native loader)."""

    def __init__(
        self,
        dataset,
        batch_size: int,
        seed: int = 0,
        start_step: int = 0,
        sharding=None,
        prefetch: int = 2,
        stall_timeout: Optional[float] = None,
        retry: Optional[RetryPolicy] = None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.seed = seed
        self.step = start_step
        self.sharding = sharding
        self.stall_timeout = stall_timeout
        self._retry = (
            retry
            if retry is not None
            else RetryPolicy(attempts=3, base_delay=0.05, max_delay=1.0)
        )
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self._fetch_step = start_step  # what the worker is on (diagnosis)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        try:
            self._worker_loop()
        except BaseException as e:  # kept for __next__ to chain, tb intact
            self._exc = e

    def _worker_loop(self):
        step = self.step
        multihost = jax.process_count() > 1
        while not self._stop.is_set():
            self._fetch_step = step

            def fetch(step=step):
                fire("data.batch", step=step)
                return self.dataset.batch(self.seed, step, self.batch_size)

            host = call_with_retries(
                fetch, self._retry, describe=f"data batch fetch (step {step})"
            )
            if self.sharding is not None and multihost:
                # multi-host: a plain device_put of globally-sharded data
                # would need non-addressable devices. Sampling is a pure
                # function of (seed, step, row), so every process assembles
                # the same global batch and materializes only the shards it
                # owns — no cross-host data exchange, bit-identical global
                # array (SURVEY.md P7).
                batch = jax.make_array_from_callback(
                    host.shape, self.sharding, lambda idx: host[idx]
                )
            elif self.sharding is not None:
                batch = jax.device_put(host, self.sharding)
            else:
                batch = jax.device_put(host)
            # block while the queue is full, but wake up on stop
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def __iter__(self) -> Iterator[Array]:
        return self

    def __next__(self) -> Array:
        # how many batches stood ready when the loop asked: 0 is a loop
        # that waits for the prefetch thread (obs/trace.py step spans)
        note_open("train.next_batch", ready=self._q.qsize())
        deadline = (
            time.monotonic() + self.stall_timeout
            if self.stall_timeout
            else None
        )
        while True:
            wait = 1.0
            if deadline is not None:
                wait = max(0.02, min(1.0, deadline - time.monotonic()))
            try:
                return self._q.get(timeout=wait)
            except queue.Empty:
                if self._exc is not None or not self._thread.is_alive():
                    raise RuntimeError(
                        "data prefetch thread died at step "
                        f"{self._fetch_step}"
                    ) from self._exc
                if deadline is not None and time.monotonic() >= deadline:
                    raise StallError(
                        "data loader stalled: no batch for "
                        f"{self.stall_timeout:.1f}s (prefetch worker alive "
                        f"but stuck fetching step {self._fetch_step} — "
                        "hung dataset read?)"
                    )

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)


def make_dataset(spec: str, seq_len: int, vocab_size: Optional[int] = None):
    """'synthetic', a token-bin path, a directory of ``shard_*.bin``, or a
    comma-separated shard list. Token-bin paths ride the C++ loader
    (runtime/loader.cc) when the .so is present — batch-for-batch identical
    to the Python fallback (contract: tests/test_runtime.py)."""
    if spec == "synthetic":
        return SyntheticDataset(vocab_size or 256, seq_len)
    if "," in spec:
        return ShardedTokenBinDataset(
            [p for p in spec.split(",") if p], seq_len
        )
    if os.path.isdir(spec):
        import glob

        paths = sorted(glob.glob(os.path.join(spec, "shard_*.bin")))
        assert paths, f"{spec}: no shard_*.bin files (corpusgen layout)"
        return ShardedTokenBinDataset(paths, seq_len)
    from orion_tpu.runtime import make_fastest_dataset

    return make_fastest_dataset(spec, seq_len)


__all__ = [
    "TokenBinDataset",
    "ShardedTokenBinDataset",
    "SyntheticDataset",
    "DataLoader",
    "write_token_bin",
    "make_dataset",
]

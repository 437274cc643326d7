"""Training data pipeline: a deterministic, seekable synthetic stream for
DLRM training, with background host prefetch.

Production input pipelines are keyed by (shard, step) so any step is
reproducible and restartable from a checkpointed step counter -- the same
property is kept here: `batch_at(step)` is a pure function of (seed, step).
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from repro.core import features as F


class DLRMBatchStream:
    """Synthetic CTR batches for a table pool (indices + dense + label)."""

    def __init__(self, raw_features: np.ndarray, batch: int,
                 n_dense: int = 13, pool_slots: int = 16, seed: int = 0):
        self.raw = raw_features
        self.batch = batch
        self.n_dense = n_dense
        self.pool_slots = pool_slots
        self.seed = seed
        self.hashes = raw_features[:, F.HASH_SIZE].astype(np.int64)
        self.pools = np.minimum(
            raw_features[:, F.POOLING].astype(np.int64) + 1, pool_slots)

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        M = self.raw.shape[0]
        idx = np.full((self.batch, M, self.pool_slots), -1, np.int32)
        for t in range(M):
            draws = rng.zipf(1.5, size=(self.batch, self.pools[t]))
            idx[:, t, :self.pools[t]] = (draws % self.hashes[t]).astype(
                np.int32)
        return {
            "indices": idx,
            "dense": rng.normal(size=(self.batch, self.n_dense)).astype(
                np.float32),
            "labels": (rng.random(self.batch) < 0.3).astype(np.float32),
        }


class Prefetcher:
    """Background-thread host prefetch over any `batch_at(step)` stream."""

    def __init__(self, stream, depth: int = 2, start_step: int = 0):
        self.stream = stream
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = start_step
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self):
        step = self._step
        while not self._stop.is_set():
            try:
                self._q.put(self.stream.batch_at(step), timeout=0.2)
                step += 1
            except queue.Full:
                continue

    def next(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=2)

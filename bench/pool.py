"""The synthetic DLRM table pool, with each table's Zipf exponent.

A copy of ``repro.data.synthetic.make_pool`` (``dim_mode="dlrm"``) and of
the feature packing it uses, kept with the benchmark so that a change to
the program cannot move the tables a cell runs.  It draws the same numbers
in the same order, so ``make_pool(856, 0)[0]`` equals
``make_dlrm_pool(0)`` byte for byte, and it also returns the exponent
``s`` behind each table's access histogram, which the traffic generator
draws its indices from.

Raw features per table (21 columns): dim, hash size, pooling factor,
size in GB, then a 17-bin access-count histogram.
"""

from __future__ import annotations

import numpy as np

NUM_FEATURES = 21
NUM_DIST_BINS = 17
DIM, HASH_SIZE, POOLING, TABLE_SIZE_GB, DIST_START = 0, 1, 2, 3, 4


def _zipf_distribution(rng: np.random.Generator, hash_size: float,
                       pooling: float, batch: int = 65536):
    """(17-bin access-count histogram, exponent s) of a zipf(s) stream."""
    s = rng.uniform(0.35, 1.7)
    n = int(min(hash_size, 2e5))
    ranks = np.unique(np.round(np.logspace(0, np.log10(n), 400))
                      .astype(np.int64))
    weights = ranks.astype(np.float64) ** (-s)
    widths = np.diff(np.concatenate([ranks, [n + 1]])).astype(np.float64)
    mass = weights * widths
    counts = batch * pooling * weights / mass.sum()
    edges = np.concatenate([[0.0], 2.0 ** np.arange(NUM_DIST_BINS - 1),
                            [np.inf]])
    hist = np.zeros(NUM_DIST_BINS)
    bin_idx = np.clip(np.searchsorted(edges, counts, side="left") - 1,
                      0, NUM_DIST_BINS - 1)
    np.add.at(hist, bin_idx, mass)
    return hist / hist.sum(), s


def make_pool(n_tables: int = 856, seed: int = 0):
    """(raw features (M, 21), zipf exponents (M,)) of the dim-16 pool."""
    rng = np.random.default_rng(seed)
    hash_size = np.round(np.clip(rng.lognormal(np.log(8e5), 1.2, n_tables),
                                 1e4, 2e7))
    pooling = np.clip((rng.pareto(1.2, n_tables) + 1.0) * 3.0, 1.0, 200.0)
    dim = np.full(n_tables, 16.0)
    drawn = [_zipf_distribution(rng, h, p) for h, p in zip(hash_size, pooling)]
    raw = np.zeros((n_tables, NUM_FEATURES))
    raw[:, DIM] = dim
    raw[:, HASH_SIZE] = hash_size
    raw[:, POOLING] = pooling
    raw[:, TABLE_SIZE_GB] = dim * hash_size * 2 / 1e9
    raw[:, DIST_START:] = np.stack([h for h, _ in drawn])
    return raw, np.array([s for _, s in drawn])

"""How ``correct`` is decided for a step cell.

Set-up drives the timed step from the seed through its first three
steps, each on a fresh batch (0, 1, 2), and reads:

- the loss of each step;
- the first step's gradient per leaf, as the optimizers got it, worked
  out from their state after that step: row-wise Adagrad's accumulator
  holds the row mean of g**2, so ``|g| = sqrt(dim * sum(acc))``; Adam's
  first moment holds ``(1 - b1) g``;
- the change of every leaf after the three steps, against the seed's
  initial weights made again;
- a sample, drawn from the seed, of the first batch's lookups, and the
  arena rows behind them with their accumulators after the first step.

Once the window has closed, the plain reference (``bench.reference``)
runs the same three steps, and ``compare`` turns the two sets of
readings into the numbers below, each held to its limit in
``bench/limits/<workload>.json``:

- ``loss_gap``: the widest relative gap of a step's loss;
- ``grad_gap``: the widest gap between the program's norm of a leaf's
  first gradient and the reference's, over the larger of that leaf's
  reference norm and the median leaf's;
- ``change_gap``: the same gap for each leaf's change after the three
  steps, and the median over the leaves, leaving out leaves whose
  reference gradient is under a thousandth of the median leaf's.  Not
  the widest: that is one of two small leaves, ``bottom.0.w`` (13 x 512
  at He scale 0.39) and ``bottom.3.w`` (64 x 16 at 0.18), whose Adam
  steps of about 1e-3 lie within about one bf16 ulp of their values, so
  that their entries move double or not at all and the gap swings from
  0.04 to 0.17 from seed to seed;
- ``row_gap``: for each table, the median over its sampled rows of the
  norm of the row's difference from the reference over the norm of the
  reference's change of that row; the worst table's;
- ``acc_gap``: for each table, the relative gap between the sums of its
  sampled rows' accumulators; the worst table's.

The sampled rows are read after the first step: by the second, every
touched row has moved five times its initial norm (row-wise Adagrad takes
a full step of ``lr * sqrt(dim)`` whatever the gradient's size), the
loss jumps, and a row's later gradients carry every other row's bf16
rounding.  ``row_gap`` takes each table's median, so that the few rows
whose true gradient cancels to nearly nothing, where a bf16 gradient is
noise that Adagrad scales up to a full step, do not decide it, while a
table whose rows all go wrong does; ``acc_gap`` sums over a table's rows
for the same reason.
"""

from __future__ import annotations

import numpy as np

from bench.layout import Layout
from bench.reference import Readings

STILL = 1e-3        # a leaf whose gradient is under this share of the
                    # median leaf's moves by round-off alone
N_SAMPLE = 8192


def sample_rows(seed: int, layout: Layout, pools: np.ndarray,
                gidx) -> tuple:
    """(shard ids, arena rows, table ids) behind ``N_SAMPLE`` live lookups
    of the (B, S*K, P) index array ``gidx``, drawn from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
    live = np.flatnonzero(pools > 0)
    b = rng.integers(0, gidx.shape[0], N_SAMPLE)
    j = live[rng.integers(0, live.size, N_SAMPLE)]
    p = rng.integers(0, pools[j])
    idx = np.asarray(gidx[b, j, p])
    shard, k = j // layout.k_max, j % layout.k_max
    return (shard.astype(np.int32),
            (layout.base_rows[shard, k] + idx).astype(np.int32),
            layout.slot_table.reshape(-1)[j])


def _leaf_gaps(got: dict, ref: dict, leaves) -> list:
    scale = float(np.median([ref[k] for k in ref]))
    return [abs(got[k] - ref[k]) / max(ref[k], scale) for k in leaves]


def _worst_table(err: np.ndarray, tables: np.ndarray) -> float:
    return float(max(np.median(err[tables == t]) for t in np.unique(tables)))


def compare(got: Readings, ref: Readings, rows0: np.ndarray,
            tables: np.ndarray) -> dict:
    grad_median = float(np.median(list(ref.grad_norms.values())))
    moving = [k for k, g in ref.grad_norms.items()
              if g >= STILL * grad_median]
    moved = np.linalg.norm(ref.rows - rows0, axis=1)
    row_err = np.linalg.norm(got.rows - ref.rows, axis=1) / moved
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got.losses, ref.losses)),
        "grad_gap": max(_leaf_gaps(got.grad_norms, ref.grad_norms,
                                   ref.grad_norms)),
        "change_gap": float(np.median(_leaf_gaps(
            got.change_norms, ref.change_norms, moving))),
        "row_gap": _worst_table(row_err, tables),
        "acc_gap": max(float(abs(got.acc[tables == t].sum()
                                 / ref.acc[tables == t].sum() - 1))
                       for t in np.unique(tables)),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; a number that is not finite fails."""
    shown = {k: {"value": float(numbers[k]), "limit": float(lim)}
             for k, lim in limits.items()}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return ok, shown

"""Placement plans: the bridge from DreamShard's assignment vector to the
physical table layout consumed by the sharded embedding op.

A ``PlacementPlan`` groups tables per shard (padding groups to a uniform
K_max), builds one per-shard arena layout (tables vertically stacked,
row 0 = zero row), and records the permutation needed to regroup the
indices tensor -- everything static/host-side so the device step stays
shape-uniform across shards.

With a column ``sharding`` (``repro.sharding.ShardSpec``) the plan's
slots hold *column shards* instead of whole tables: ``assignment`` is
then ``(S,)`` over the spec's shards, each slot still records its
OWNING table id in ``slot_table`` (a column shard consumes its owner's
full index stream, so index grouping is unchanged) plus its column
range in ``slot_cols``, and it occupies the owner's full row count in
the arena.  ``repro.embedding.sharded.combine_shard_outputs`` scatters
the per-slot outputs back into per-table columns.  Plans without a
sharding are bit-for-bit what they were before the field existed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import telemetry
from repro.core import features as F


@dataclasses.dataclass
class PlacementPlan:
    assignment: np.ndarray        # (M,) table -> shard ((S,) when sharded)
    n_shards: int
    dim: int                      # padded feature dim (128-lane multiple)
    k_max: int                    # tables per shard (padded)
    rows_max: int                 # arena rows per shard (padded, incl. zero row)
    groups: list[np.ndarray]      # table ids per shard (unpadded; column-shard
                                  # ids when sharded)
    base_rows: np.ndarray         # (n_shards, k_max) arena base row per slot
    slot_table: np.ndarray        # (n_shards, k_max) OWNING table id or -1
    table_rows: np.ndarray        # (M,) rows per table
    sharding: object | None = None   # ShardSpec behind a column-sharded plan
    slot_cols: np.ndarray | None = None  # (n_shards, k_max, 2) [start, end)
    bag_widths: np.ndarray | None = None  # (n_shards, k_max) ids a sample
    col_slot: np.ndarray | None = None    # (n_shards, n_cols) slot or -1

    @property
    def n_cols(self) -> int:
        """W: index columns per shard of a plan with bag widths."""
        return int(self.col_slot.shape[1])

    def col_ranges(self, s: int) -> list[tuple[int, int]]:
        """[start, end) index columns of each of shard ``s``'s slots."""
        ends = np.cumsum(self.bag_widths[s])
        return [(int(e - w), int(e)) for e, w in zip(ends,
                                                     self.bag_widths[s])]

    @property
    def n_tables(self) -> int:
        if self.sharding is not None:
            return self.sharding.n_tables
        return self.assignment.shape[0]

    @property
    def is_sharded(self) -> bool:
        return self.sharding is not None

    def grouped_index_order(self) -> np.ndarray:
        """(n_shards * k_max,) owning table id per grouped slot (-1 =
        padding).  Column shards repeat their owner: every shard of a
        table routes the SAME index stream."""
        return self.slot_table.reshape(-1)


def build_plan(raw_features: np.ndarray, assignment: np.ndarray,
               n_shards: int, pad_dim_to: int = 128,
               sharding=None, widths=None,
               pad_rows_to: int = 1) -> PlacementPlan:
    """``widths``: (M,) ids per sample of each table (its fixed multi-hot
    size), for the column layout; ``pad_rows_to``: round ``rows_max`` up
    to a multiple of this (the row update writes bf16 rows in pairs, and
    at a multiple of 16 its pair view of the arena is a bitcast)."""
    assignment = np.asarray(assignment)
    rows = raw_features[:, F.HASH_SIZE].astype(np.int64)
    dim = int(raw_features[:, F.DIM].max())
    dimp = int(np.ceil(dim / pad_dim_to) * pad_dim_to)
    # owner[i]: the table behind grouped item i (identity when unsharded)
    owner = np.arange(rows.shape[0]) if sharding is None else sharding.table
    if assignment.shape[0] != owner.shape[0]:
        raise ValueError(
            f"assignment covers {assignment.shape[0]} items, expected "
            f"{owner.shape[0]} ({'shards' if sharding is not None else 'tables'})")
    groups = [np.flatnonzero(assignment == s) for s in range(n_shards)]
    k_max = max(1, max(len(g) for g in groups))
    rows_max = 1 + max(int(rows[owner[g]].sum()) if len(g) else 0
                       for g in groups)
    rows_max = -(-rows_max // pad_rows_to) * pad_rows_to

    base = np.zeros((n_shards, k_max), np.int64)
    slot = np.full((n_shards, k_max), -1, np.int64)
    cols = None
    if sharding is not None:
        cols = np.zeros((n_shards, k_max, 2), np.int64)
    for s, g in enumerate(groups):
        r = 1                                          # row 0 reserved zero
        for j, i in enumerate(g):
            base[s, j] = r
            slot[s, j] = owner[i]
            if cols is not None:
                cols[s, j] = (sharding.col_start[i], sharding.col_end[i])
            r += int(rows[owner[i]])
    bag_widths = col_slot = None
    if widths is not None:
        widths = np.asarray(widths, np.int64)
        if sharding is not None or widths.shape != rows.shape \
                or (widths < 1).any():
            raise ValueError("bag widths need an unsharded plan and one "
                             "positive width per table")
        bag_widths = np.where(slot >= 0, widths[np.maximum(slot, 0)], 0)
        n_cols = int(bag_widths.sum(axis=1).max())
        col_slot = np.full((n_shards, n_cols), -1, np.int64)
        for s in range(n_shards):
            col_slot[s, :bag_widths[s].sum()] = np.repeat(
                np.arange(k_max), bag_widths[s])
        telemetry.count("plan.bag_columns", n_shards * n_cols)
        telemetry.count("plan.live_columns", int(bag_widths.sum()))
    return PlacementPlan(assignment=assignment, n_shards=n_shards, dim=dimp,
                         k_max=k_max, rows_max=rows_max, groups=groups,
                         base_rows=base, slot_table=slot, table_rows=rows,
                         sharding=sharding, slot_cols=cols,
                         bag_widths=bag_widths, col_slot=col_slot)

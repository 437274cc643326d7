"""The placed arena layout, derived from the placement alone.

Given which shard holds each table, the step's layout is fixed: each
shard stacks its tables' rows in table order under a reserved row 0, the
slot groups are padded to the widest shard (``k_max`` slots), and the
batch holds indices as ``(B, n_shards * k_max, P)`` with ``-1`` padding.
The benchmark derives this itself, for its traffic, its reference and its
checks, and compares it with the program's plan in set-up.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Layout:
    n_shards: int
    k_max: int
    rows_max: int              # arena rows per shard, row 0 included
    slot_table: np.ndarray     # (n_shards, k_max) table id, -1 = padding
    base_rows: np.ndarray      # (n_shards, k_max) first arena row of a slot
    table_rows: np.ndarray     # (M,) rows per table

    @property
    def n_slots(self) -> int:
        return self.n_shards * self.k_max

    @property
    def n_tables(self) -> int:
        return int(self.table_rows.shape[0])

    def shard_rows(self) -> np.ndarray:
        """Arena rows in use per shard, row 0 included."""
        rows = np.where(self.slot_table >= 0,
                        self.table_rows[np.maximum(self.slot_table, 0)], 0)
        return 1 + rows.sum(axis=1)

    def table_order(self) -> np.ndarray:
        """Slot of each table, tables in id order: (M,) indices into the
        ``n_slots`` grouped slots."""
        flat = self.slot_table.reshape(-1)
        keep = np.flatnonzero(flat >= 0)
        return keep[np.argsort(flat[keep], kind="stable")]


def make_layout(assignment: np.ndarray, table_rows: np.ndarray,
                n_shards: int) -> Layout:
    assignment = np.asarray(assignment)
    table_rows = np.asarray(table_rows, np.int64)
    groups = [np.flatnonzero(assignment == s) for s in range(n_shards)]
    k_max = max(1, max(len(g) for g in groups))
    slot = np.full((n_shards, k_max), -1, np.int64)
    base = np.zeros((n_shards, k_max), np.int64)
    for s, g in enumerate(groups):
        slot[s, :len(g)] = g
        base[s, :len(g)] = 1 + np.cumsum(table_rows[g]) - table_rows[g]
    rows_max = 1 + max(int(table_rows[g].sum()) for g in groups)
    return Layout(n_shards, k_max, rows_max, slot, base, table_rows)


def matches_plan(layout: Layout, plan) -> bool:
    """True when the program's ``PlacementPlan`` lays the arenas out as
    ``layout`` does."""
    return (plan.n_shards == layout.n_shards and plan.k_max == layout.k_max
            and plan.rows_max == layout.rows_max
            and np.array_equal(plan.slot_table, layout.slot_table)
            and np.array_equal(plan.base_rows, layout.base_rows))

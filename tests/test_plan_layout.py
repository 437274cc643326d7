"""Layout invariants of a ``PlacementPlan``, on host arrays only.

For each pool (dim-16 DLRM, mixed-dim production), shard count and index
layout (padded ``(B, S*K, P)`` or per-table bag widths), a plan built
from an assignment that may leave shards empty must: give every table a
disjoint row range of its shard's arena that no live id maps to row 0,
size the arenas to cover every shard in multiples of ``pad_rows_to``,
hand each table its own ids back through ``group_indices`` and
``shard_indices``, and, with widths, give each slot a run of columns of
its table's width."""

import numpy as np
import pytest

from repro.core import features as F
from repro.embedding.plan import build_plan
from repro.embedding.sharded import group_indices, shard_indices

M, B, P, PAD_ROWS = 12, 6, 5, 16


def widths_of(raw):
    return np.minimum(raw[:, F.POOLING].astype(np.int64) + 1, P)


def ids_for(raw, widths, seed):
    """(B, M, P) ids below each table's rows, -1 padded; bags of every
    length up to the table's width, some of them empty."""
    rng = np.random.default_rng(seed)
    ids = np.full((B, M, P), -1, np.int64)
    for t in range(M):
        n = rng.integers(0, widths[t] + 1, B)
        n[0] = 0
        for b in range(B):
            ids[b, t, :n[b]] = rng.integers(0, raw[t, F.HASH_SIZE], n[b])
    return ids


@pytest.fixture(params=["dlrm", "prod"])
def raw(request, dlrm_pool, prod_pool):
    return (dlrm_pool if request.param == "dlrm" else prod_pool)[:M]


@pytest.mark.parametrize("layout", ["padded", "widths"])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8])
def test_plan_layout_invariants(raw, n_shards, layout):
    assign = np.random.default_rng(n_shards).integers(0, n_shards, M)
    widths = widths_of(raw)
    plan = build_plan(raw, assign, n_shards, pad_dim_to=16,
                      widths=widths if layout == "widths" else None,
                      pad_rows_to=PAD_ROWS)
    rows = raw[:, F.HASH_SIZE].astype(np.int64)
    assert plan.dim == -(-int(raw[:, F.DIM].max()) // 16) * 16

    # each table in one slot of its own shard, on a disjoint row range
    # that starts past the reserved row 0
    need = []
    for s in range(n_shards):
        live = [k for k in range(plan.k_max) if plan.slot_table[s, k] >= 0]
        tables = sorted(int(plan.slot_table[s, k]) for k in live)
        assert tables == sorted(np.flatnonzero(assign == s).tolist())
        spans = sorted((int(plan.base_rows[s, k]),
                        int(plan.base_rows[s, k])
                        + int(rows[plan.slot_table[s, k]])) for k in live)
        assert all(lo >= 1 for lo, _ in spans)
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
        need.append(max([hi for _, hi in spans], default=1))
        assert (plan.slot_table[s, len(live):] == -1).all()

    # the arenas cover the fullest shard, in whole multiples of the pad
    assert plan.rows_max % PAD_ROWS == 0
    assert max(need) <= plan.rows_max < max(need) + PAD_ROWS

    ids = ids_for(raw, widths, n_shards)
    gidx = group_indices(plan, ids)
    for s in range(n_shards):
        own = shard_indices(plan, gidx, s)
        for k in range(plan.k_max):
            t = int(plan.slot_table[s, k])
            if layout == "padded":
                got = own[:, k]
                want = ids[:, t] if t >= 0 else np.full((B, P), -1)
            else:
                c0, c1 = plan.col_ranges(s)[k]
                got = own[:, c0:c1]
                want = ids[:, t, :c1 - c0]
            np.testing.assert_array_equal(got, want)
            # no live id lands on the reserved row 0
            live = got >= 0
            assert (got[live] + plan.base_rows[s, k] >= 1).all()
            assert (got[live] < rows[t]).all()

    if layout == "widths":
        assert plan.n_cols == max(int(widths[assign == s].sum())
                                  for s in range(n_shards))
        assert gidx.shape == (B, n_shards * plan.n_cols)
        for s in range(n_shards):
            for k, (c0, c1) in enumerate(plan.col_ranges(s)):
                t = int(plan.slot_table[s, k])
                assert c1 - c0 == plan.bag_widths[s, k] \
                    == (widths[t] if t >= 0 else 0)
                assert (plan.col_slot[s, c0:c1] == k).all()
                assert (plan.col_slot[s] == k).sum() == c1 - c0
            used = int(plan.bag_widths[s].sum())
            assert (plan.col_slot[s, used:] == -1).all()
    else:
        assert plan.col_slot is None
        assert gidx.shape == (B, n_shards * plan.k_max, P)

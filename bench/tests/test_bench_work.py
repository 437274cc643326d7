"""Required work from shapes and indices; peaks by device kind."""

import numpy as np
import pytest

from bench import work
from bench.layout import make_layout
from bench.peaks import peaks


def tiny():
    # tables 0, 2 on shard 0; table 1 on shard 1; 5, 7, 3 rows
    layout = make_layout(np.array([0, 1, 0]), np.array([5, 7, 3]), 2)
    gidx = -np.ones((2, 4, 3), np.int64)          # (B, S*K, P)
    gidx[0, 0, :2] = [1, 4]     # table 0, rows 1, 4
    gidx[1, 0, :1] = [1]        # table 0 row 1 again
    gidx[0, 1, :1] = [2]        # table 2 row 2
    gidx[1, 2, :3] = [0, 6, 6]  # table 1 rows 0, 6, 6
    return layout, gidx


def test_layout_stacks_tables_under_a_zero_row():
    layout, _ = tiny()
    assert layout.k_max == 2 and layout.rows_max == 1 + 5 + 3
    assert layout.slot_table.tolist() == [[0, 2], [1, -1]]
    assert layout.base_rows.tolist() == [[1, 6], [1, 0]]
    assert layout.table_order().tolist() == [0, 2, 1]


def test_counts_equal_hand_counts():
    layout, gidx = tiny()
    live, distinct = work.count_lookups(gidx, layout.k_max,
                                        layout.base_rows)
    assert live == 7
    # shard 0: rows 1+1, 4+1, 2+6 ; shard 1: rows 0+1, 6+1
    assert distinct == 5
    sizes = {"bottom": [3, 4, 2], "top": [5, 1]}
    w = work.step_work(batch=2, n_tables=3, dim=2, sizes=sizes,
                       live_lookups=live, distinct_rows=distinct,
                       row_bytes=2, acc_bytes=2, pooled_bytes=4)
    mlp = (2 * 2 * 2 * 3 * 4          # bottom 0: forward + weight grad
           + 3 * 2 * 2 * 4 * 2        # bottom 1
           + 3 * 2 * 2 * 5 * 1)       # top 0
    pairs = 4 * 3 // 2
    assert w.flops == mlp + 3 * 2 * 2 * 2 * pairs
    pooled = 2 * 3 * 2 * 4
    assert w.emb_fwd_bytes == 7 * 2 * 2 + pooled
    assert w.emb_bwd_bytes == pooled + 5 * 2 * (2 * 2 + 2)
    assert w.step_bytes == w.emb_fwd_bytes + w.emb_bwd_bytes + 2 * pooled


def test_a_repeated_row_adds_forward_bytes_but_no_update_bytes():
    layout, gidx = tiny()
    more = gidx.copy()
    more[0, 0, 2] = 1            # table 0 row 1, already looked up
    a = work.count_lookups(gidx, layout.k_max, layout.base_rows)
    b = work.count_lookups(more, layout.k_max, layout.base_rows)
    assert b == (a[0] + 1, a[1])
    kw = dict(batch=2, n_tables=3, dim=2, sizes={"bottom": [1, 2]},
              row_bytes=2, acc_bytes=2, pooled_bytes=4)
    wa = work.step_work(live_lookups=a[0], distinct_rows=a[1], **kw)
    wb = work.step_work(live_lookups=b[0], distinct_rows=b[1], **kw)
    assert wb.emb_fwd_bytes == wa.emb_fwd_bytes + 2 * 2
    assert wb.emb_bwd_bytes == wa.emb_bwd_bytes


def test_device_count_matches_host_count():
    import jax.numpy as jnp
    layout, gidx = tiny()
    count = work.device_count_fn(layout.k_max, layout.base_rows,
                                 layout.rows_max)
    got = tuple(int(x) for x in count(jnp.asarray(gidx, jnp.int32)))
    assert got == work.count_lookups(gidx, layout.k_max, layout.base_rows)


def test_peaks_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")

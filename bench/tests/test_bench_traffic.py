"""The Zipf batch generator keeps to each table's rows and pooling."""

import numpy as np

from bench.layout import make_layout
from bench.pool import HASH_SIZE, POOLING, make_pool
from bench.traffic_gen import key_of, make_batch_fn, slot_params


def small_cell(max_pooling=16, law="zipf"):
    raw, zipf = make_pool(856, 0)
    raw, zipf = raw[:12].copy(), zipf[:12]
    raw[:, HASH_SIZE] = np.minimum(raw[:, HASH_SIZE], 3000)
    layout = make_layout(np.arange(12) % 3, raw[:, HASH_SIZE], 3)
    return raw, layout, slot_params(layout, raw, zipf, max_pooling, law)


def test_indices_respect_rows_and_pooling():
    raw, layout, params = small_cell()
    build = make_batch_fn(params, 512, 13, 16, 0.3)
    gidx = np.asarray(build(key_of(5, "batch", 0))["gidx"])
    assert gidx.shape == (512, layout.n_slots, 16)
    for j, t in enumerate(layout.slot_table.reshape(-1)):
        bag = gidx[:, j]
        if t < 0:
            assert (bag == -1).all()
            continue
        pool = int(np.clip(np.rint(raw[t, POOLING]), 1, 16))
        assert (bag[:, :pool] >= 0).all() and (bag[:, pool:] == -1).all()
        assert bag[:, :pool].max() < raw[t, HASH_SIZE]


def test_hot_rows_are_skewed_and_scattered():
    raw, layout, params = small_cell()
    build = make_batch_fn(params, 2048, 13, 16, 0.3)
    gidx = np.asarray(build(key_of(9, "batch", 0))["gidx"])
    j = int(np.argmax(params["s"] * (params["pool"] > 0)))
    vals = gidx[:, j][gidx[:, j] >= 0]
    counts = np.bincount(vals)
    top = np.argsort(-counts)[:4]
    assert counts[top[0]] > 20 * vals.size / params["rows"][j]
    assert np.abs(np.diff(np.sort(top))).min() > 1     # not contiguous


def test_same_seed_same_batch_any_seed_same_shapes():
    _, _, params = small_cell()
    build = make_batch_fn(params, 64, 13, 16, 0.3)
    a = build(key_of(2**31 + 77, "batch", 1))
    b = build(key_of(2**31 + 77, "batch", 1))
    c = build(key_of(3, "batch", 1))
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert a[k].shape == c[k].shape
    assert not np.array_equal(np.asarray(a["gidx"]), np.asarray(c["gidx"]))
    assert ((np.asarray(c["gidx"]) >= 0).sum()
            == (np.asarray(a["gidx"]) >= 0).sum())


def test_uniform_law_spreads_indices_evenly():
    raw, layout, params = small_cell(law="uniform")
    build = make_batch_fn(params, 2048, 13, 16, 0.3)
    gidx = np.asarray(build(key_of(9, "batch", 0))["gidx"])
    j = int(np.argmax(params["pool"]))
    vals = gidx[:, j][gidx[:, j] >= 0]
    counts = np.bincount(vals, minlength=params["rows"][j])
    mean = vals.size / params["rows"][j]
    assert counts.max() < mean + 8 * np.sqrt(mean) + 8

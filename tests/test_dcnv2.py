"""DLRM-DCNv2 on the placed step: the DCN-v2 cross interaction, per-table
bag widths (the column layout), and row-wise Adagrad on touched rows.

Each is checked at a small size on seeded weights against plain forms:
the model against ``dlrm_reference`` (f32, HIGHEST precision, autodiff);
the column lookup against a gather and sum per table; the row update
against the dense cotangent plus ``rowwise_adagrad`` with f32
accumulators, with the Pallas kernels in interpret mode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlrm_reference as R
from repro.core import features as F
from repro.data.synthetic import make_dlrm_pool
from repro.embedding import sharded as E
from repro.embedding.plan import PlacementPlan, build_plan
from repro.kernels.embedding_bag import backward as BK
from repro.kernels.embedding_bag import row_update as RK
from repro.models import dlrm
from repro.optim import RowWiseAdagrad, adam, rowwise_adagrad

LR, EPS = 0.05, 1e-8
ROWS = 300


def small_raw(m: int):
    raw = make_dlrm_pool(seed=0)[:m].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, ROWS)
    return raw


def multihot_ids(widths, batch, seed, hot=None):
    """(B, M, max width) ids: exactly ``widths[t]`` live ids for table t,
    -1 after; ``hot`` = (table, row) puts that row in half its ids."""
    rng = np.random.default_rng(seed)
    ids = np.full((batch, len(widths), max(widths)), -1, np.int32)
    for t, w in enumerate(widths):
        ids[:, t, :w] = rng.integers(0, ROWS, (batch, w))
    if hot is not None:
        t, row = hot
        w = widths[t]
        ids[:, t, :w] = np.where(rng.random((batch, w)) < 0.5, row,
                                 ids[:, t, :w])
    return ids


# widths 1 and 100 in one shard (the first), and a shard narrower than W
WIDTHS = np.array([1, 100, 3, 2, 7, 1, 12, 5])
ASSIGN = np.array([0, 0, 1, 1, 1, 0, 1, 1])


@pytest.fixture(scope="module")
def col_plan():
    return build_plan(small_raw(len(WIDTHS)), ASSIGN, 2, widths=WIDTHS,
                      pad_rows_to=2)


def test_plan_without_widths_is_todays_plan(col_plan):
    raw = small_raw(len(WIDTHS))
    plain = build_plan(raw, ASSIGN, 2)
    assert plain.bag_widths is None and plain.col_slot is None
    old = [f.name for f in dataclasses.fields(PlacementPlan)
           if f.name not in ("bag_widths", "col_slot")]
    unpadded = build_plan(raw, ASSIGN, 2, widths=WIDTHS)
    for name in old:
        a, b = getattr(plain, name), getattr(unpadded, name)
        if isinstance(a, list):
            assert all(np.array_equal(x, y) for x, y in zip(a, b)), name
        else:
            assert np.array_equal(a, b) if a is not None else b is None, name
    # today's layout: tables stacked in id order under row 0, per shard
    for s in range(2):
        tables = np.flatnonzero(ASSIGN == s)
        rows = plain.table_rows[tables]
        np.testing.assert_array_equal(
            plain.base_rows[s, :len(tables)], 1 + np.cumsum(rows) - rows)
    assert plain.rows_max == 1 + max(plain.table_rows[ASSIGN == s].sum()
                                     for s in range(2))
    assert col_plan.rows_max % 2 == 0 and col_plan.rows_max - 1 <= \
        plain.rows_max


def test_column_layout_maps_each_column_to_its_slot(col_plan):
    plan = col_plan
    assert plan.n_cols == max(WIDTHS[ASSIGN == s].sum() for s in range(2))
    for s in range(2):
        live = WIDTHS[ASSIGN == s].sum()
        assert (plan.col_slot[s, live:] == -1).all()
        for k, (c0, c1) in enumerate(plan.col_ranges(s)):
            t = plan.slot_table[s, k]
            assert c1 - c0 == (WIDTHS[t] if t >= 0 else 0)
            assert (plan.col_slot[s, c0:c1] == k).all()


def test_plan_counts_its_columns(telemetry):
    build_plan(small_raw(len(WIDTHS)), ASSIGN, 2, widths=WIDTHS)
    w = max(WIDTHS[ASSIGN == s].sum() for s in range(2))
    assert telemetry.counter_value("plan.bag_columns") == 2 * w
    assert telemetry.counter_value("plan.live_columns") == WIDTHS.sum()


def _lookup(plan):
    return lambda a, i: E.lookup_unsharded(a, plan.base_rows, i, plan)


@pytest.mark.parametrize("seed", [0, 1])
def test_column_lookup_matches_gather_and_sum(col_plan, seed):
    plan = col_plan
    arenas = E.init_arenas(jax.random.PRNGKey(seed), plan)
    ids = multihot_ids(WIDTHS, 16, seed)
    got = _lookup(plan)(arenas, jnp.asarray(E.group_indices(plan, ids)))
    want = R.pooled(R.table_arenas(arenas, plan), jnp.asarray(ids))
    order = plan.grouped_index_order()
    for slot, t in enumerate(order):
        if t >= 0:
            np.testing.assert_allclose(got[:, slot], want[:, t], rtol=1e-6,
                                       atol=1e-7)
        else:
            assert not np.asarray(got[:, slot]).any()


def test_column_lookup_backward_matches_gather_transpose(col_plan):
    plan = col_plan
    arenas = E.init_arenas(jax.random.PRNGKey(0), plan)
    ids = multihot_ids(WIDTHS, 16, 2, hot=(1, 7))
    gidx = jnp.asarray(E.group_indices(plan, ids))
    order = plan.grouped_index_order()
    cot = np.random.default_rng(3).normal(size=(16, len(order), plan.dim))
    got = jax.grad(lambda a: jnp.sum(_lookup(plan)(a, gidx) * cot))(arenas)
    live = np.flatnonzero(order >= 0)

    def plain(a):
        p = R.pooled(R.table_arenas(a, plan), jnp.asarray(ids))
        return jnp.sum(p[:, order[live]] * cot[:, live])

    want = jax.grad(plain)(arenas)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert not np.asarray(got[:, 0]).any()


@pytest.fixture()
def kernels(monkeypatch):
    """The TPU branches of the lookup's backward and of the row update,
    with their Pallas kernels in interpret mode."""
    def tpu_branch(*args, tpu, default):
        return tpu(*args)

    monkeypatch.setattr(E.jax.lax, "platform_dependent", tpu_branch)
    monkeypatch.setattr(BK, "sorted_row_sum", functools.partial(
        BK.sorted_row_sum, interpret=True))
    monkeypatch.setattr(RK, "write_rows", functools.partial(
        RK.write_rows, interpret=True))


def _dense_update(arena, idx, bases, g, col_slot):
    """The dense f32 cotangent of the lookup, then ``rowwise_adagrad``
    with f32 accumulators: (arena, acc) after one step."""
    a32 = arena.astype(jnp.float32)
    _, vjp = jax.vjp(lambda a: E._local_lookup(a, bases, idx, col_slot),
                     a32)
    grad = vjp(g)[0]
    opt = rowwise_adagrad(LR, eps=EPS)
    upd, state = opt.update({"a": grad}, opt.init({"a": a32}))
    return a32 + upd["a"], state.inner["a"]


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("law", ["uniform", "hot"])
def test_row_update_matches_dense_rowwise_adagrad(request, col_plan, path,
                                                  dtype, law):
    """Shard 0 of the column plan (widths 1 and 100 side by side), with
    duplicate rows in every batch and, for ``hot``, one row in half of a
    table's ids; the touched rows, their accumulators and every other
    row after one update."""
    if path == "kernels":
        request.getfixturevalue("kernels")
    plan = col_plan
    arena = E.init_arenas(jax.random.PRNGKey(1), plan, dtype)[0]
    arena = arena.at[0].set(0)
    ids = multihot_ids(WIDTHS, 24, 5, hot=(1, 2) if law == "hot" else None)
    idx = jnp.asarray(E.group_indices(plan, ids))[:, :plan.n_cols]
    bases = jnp.asarray(plan.base_rows[0])
    g = jnp.asarray(np.random.default_rng(6).normal(
        size=(24, plan.k_max, plan.dim)), jnp.float32)
    acc0 = jnp.zeros(arena.shape[:1], jnp.float32)
    got_a, got_acc = jax.jit(functools.partial(
        E.rowwise_adagrad_rows, lr=LR, eps=EPS,
        col_slot=tuple(plan.col_slot[0])))(arena, acc0, bases, idx, g)
    want_a, want_acc = _dense_update(arena, idx, bases, g, plan.col_slot[0])
    assert got_a.dtype == dtype and got_acc.dtype == jnp.float32
    np.testing.assert_allclose(got_acc, want_acc, rtol=1e-5,
                               atol=1e-6 * float(want_acc.max()))
    touched = np.asarray(want_acc) > 0
    assert 0 < touched.sum() < arena.shape[0] - 1
    # untouched rows are bit for bit what they were
    np.testing.assert_array_equal(np.asarray(got_a)[~touched],
                                  np.asarray(arena)[~touched])
    if dtype == jnp.float32:
        np.testing.assert_allclose(got_a, want_a, rtol=1e-5, atol=1e-6)
    else:               # the f32 result rounded once to bf16
        np.testing.assert_allclose(
            np.asarray(got_a, np.float32),
            np.asarray(want_a.astype(dtype), np.float32),
            rtol=2.0 ** -7, atol=1e-6)


def test_row_update_grouped_slots_match_dense_rowwise_adagrad(kernels):
    """The (B, K, P) layout of plans without widths, padded slots and
    bags of padding included."""
    raw = small_raw(4)
    plan = build_plan(raw, np.zeros(4, int), 1, pad_rows_to=2)
    arena = E.init_arenas(jax.random.PRNGKey(2), plan, jnp.bfloat16)[0]
    arena = arena.at[0].set(0)
    rng = np.random.default_rng(7)
    ids = np.where(rng.random((16, 4, 5)) < 0.3, -1,
                   rng.integers(0, 40, (16, 4, 5))).astype(np.int32)
    idx = jnp.asarray(E.group_indices(plan, ids))
    bases = jnp.asarray(plan.base_rows[0])
    g = jnp.asarray(rng.normal(size=(16, 4, plan.dim)), jnp.float32)
    got_a, got_acc = E.rowwise_adagrad_rows(
        arena, jnp.zeros(arena.shape[:1], jnp.float32), bases, idx, g,
        lr=LR, eps=EPS)
    want_a, want_acc = _dense_update(arena, idx, bases, g, None)
    np.testing.assert_allclose(got_acc, want_acc, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(np.asarray(got_a, np.float32),
                               np.asarray(want_a.astype(jnp.bfloat16),
                                          np.float32),
                               rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("pack,dtype", [(1, jnp.float32), (2, jnp.bfloat16)])
def test_write_rows_interpret_writes_only_the_given_units(pack, dtype):
    rng = np.random.default_rng(pack)
    n_units, n = 3000, 2 * RK.CHUNK_ROWS
    table = jnp.asarray(rng.normal(size=(n_units, pack, 128)), dtype)
    units = np.sort(rng.choice(n_units, n, replace=False)).astype(np.int32)
    rows = jnp.asarray(rng.normal(size=(n, pack, 128)), dtype)
    live = n - 37                                   # a partial last chunk
    got = RK.write_rows(table, jnp.asarray(units), rows, jnp.int32(live),
                        interpret=True)
    want = np.asarray(table).copy()
    want[units[:live]] = np.asarray(rows)[:live]
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("block_rows", [256, BK.BLOCK_ROWS])
def test_row_sum_kernel_one_lane_and_small_blocks(block_rows):
    """The accumulators' sums (D = 1) and compact sums (256-row blocks)."""
    rng = np.random.default_rng(block_rows)
    n_rows, n = 3000, 2 * BK.CHUNK
    keys = np.sort(np.where(rng.random(n) < 0.2, n_rows,
                            rng.integers(0, n_rows, n))).astype(np.int32)
    for dim in (1, 128):
        grads = jnp.asarray(rng.normal(size=(n, dim)), jnp.float32)
        got = BK.sorted_row_sum(jnp.asarray(keys), grads, n_rows=n_rows,
                                dtype=jnp.float32, interpret=True,
                                block_rows=block_rows)
        want = BK.sorted_row_sum_ref(jnp.asarray(keys), grads,
                                     n_rows=n_rows, dtype=jnp.float32)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---- the model ----------------------------------------------------------

def dcn_setup(dtype=jnp.float32):
    plan = build_plan(small_raw(len(WIDTHS)), ASSIGN, 2, widths=WIDTHS,
                      pad_rows_to=2)
    cfg = dlrm.DLRMConfig(n_dense_features=4, embed_dim=plan.dim,
                          bottom_mlp=(32,), top_mlp=(64, 32),
                          n_tables=len(WIDTHS), interaction="dcn",
                          cross_layers=3, cross_rank=16)
    model = dlrm.DLRM(cfg, plan, dtype=dtype)
    params = model.init_params(jax.random.PRNGKey(0))
    params["arenas"] = params["arenas"].at[:, 0].set(0)
    # non-zero biases, so that the cross layers' bias terms are checked
    params["cross"] = [dict(c, b=jnp.full_like(c["b"], 0.1 * (i + 1)))
                       for i, c in enumerate(params["cross"])]
    rng = np.random.default_rng(0)
    ids = multihot_ids(WIDTHS, 16, 4, hot=(6, 3))
    batch = {"dense": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
             "gidx": jnp.asarray(E.group_indices(plan, ids)),
             "labels": jnp.asarray(rng.integers(0, 2, 16), jnp.float32)}
    return model, params, ids, batch


def test_dcn_config_shapes():
    cfg = dlrm.DLRMConfig(embed_dim=128, n_tables=26, interaction="dcn")
    assert cfg.top_in == 27 * 128 == 3456
    assert cfg.dense_keys == ("bottom", "cross", "top")
    assert dlrm.DLRMConfig().dense_keys == dlrm.DENSE_PARAMS
    with pytest.raises(ValueError):
        dlrm.DLRMConfig(interaction="attention")


def test_dcn_model_matches_reference():
    model, params, ids, batch = dcn_setup()
    cross = params["cross"]
    assert [(c["V"].shape, c["W"].shape, c["b"].shape)
            for c in cross] == [((9 * 128, 16), (16, 9 * 128), (9 * 128,))] * 3
    lookup = _lookup(model.plan)

    def loss(p):
        z = model.forward(p, batch["dense"], batch["gidx"],
                          lambda a, b, i: lookup(a, i))
        return dlrm.DLRM.loss(z, batch["labels"]), z

    with jax.default_matmul_precision("highest"):
        (got_loss, got_z), got_g = jax.value_and_grad(loss, has_aux=True)(
            params)
    dense = {k: v for k, v in params.items() if k != "arenas"}
    want_z = R.logits(dense, R.pooled(R.table_arenas(params["arenas"],
                                                     model.plan),
                                      jnp.asarray(ids)),
                      batch["dense"], "dcn")
    want_loss, want_gd, want_ga = R.loss_and_grads(
        params, model.plan, jnp.asarray(ids), batch["dense"],
        batch["labels"], "dcn")
    np.testing.assert_allclose(got_z, want_z, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves({k: got_g[k] for k in dense}),
                    jax.tree.leaves(want_gd)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))
    np.testing.assert_allclose(got_g["arenas"], want_ga, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want_ga).max()))


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_row_update_step_matches_reference(request, path):
    """One whole step (DCN-v2, column layout, row update): the loss, the
    arenas and their accumulators against the reference's row-wise
    Adagrad step, and the dense parameters against the dense path's."""
    if path == "kernels":
        request.getfixturevalue("kernels")
    model, params, ids, batch = dcn_setup()
    lookup = _lookup(model.plan)
    emb_opt, dense_opt = RowWiseAdagrad(LR, eps=EPS), adam(1e-3)
    step = dlrm.make_train_step(model, lambda a, b, i: lookup(a, i),
                                emb_opt, dense_opt)
    es = emb_opt.init({"arenas": params["arenas"]})
    ds = dense_opt.init({k: params[k] for k in model.cfg.dense_keys})
    with jax.default_matmul_precision("highest"):
        p, es2, ds2, loss = jax.jit(step)(params, es, ds, batch)
    want_loss, want_gd, want_ga = R.loss_and_grads(
        params, model.plan, jnp.asarray(ids), batch["dense"],
        batch["labels"], "dcn")
    want_a, want_acc = R.adagrad_step(
        params["arenas"], jnp.zeros(params["arenas"].shape[:2]), want_ga,
        LR, EPS)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(es2.inner["arenas"], want_acc, rtol=1e-4,
                               atol=1e-6 * float(want_acc.max()))
    np.testing.assert_allclose(p["arenas"], want_a, rtol=1e-5, atol=1e-6)
    assert int(es2.step) == 1
    upd, _ = dense_opt.update(want_gd, ds)
    for a, b, u in zip(jax.tree.leaves({k: p[k] for k in want_gd}),
                       jax.tree.leaves({k: params[k] for k in want_gd}),
                       jax.tree.leaves(upd)):
        np.testing.assert_allclose(a, b + u, rtol=1e-5, atol=1e-6)


def test_row_update_refuses_the_sharded_lookup():
    plan = build_plan(small_raw(4), np.arange(4) % 2, 2)
    model = dlrm.DLRM(dlrm.DLRMConfig(n_dense_features=4,
                                      embed_dim=plan.dim, n_tables=4), plan)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    one = build_plan(small_raw(4), np.zeros(4, int), 1)
    lookup = E.make_sharded_lookup(mesh, one)
    with pytest.raises(NotImplementedError):
        dlrm.make_train_step(model, lookup, RowWiseAdagrad(LR),
                             adam(1e-3))
    with pytest.raises(NotImplementedError):
        E.make_sharded_lookup(mesh, build_plan(small_raw(4), np.zeros(4, int),
                                               1, widths=[1, 2, 3, 4]))


def test_dcn_step_names_its_cross_layers_and_row_update():
    """The compiled step carries ``dlrm.cross.<l>`` under
    ``dlrm.interact`` for each layer, forward and backward, and the row
    update's scopes under ``dlrm.emb_update``."""
    from test_step_scopes import op_names, scopes_of
    model, params, ids, batch = dcn_setup(jnp.bfloat16)
    lookup = _lookup(model.plan)
    emb_opt, dense_opt = RowWiseAdagrad(LR), adam(1e-3)
    step = dlrm.make_train_step(model, lambda a, b, i: lookup(a, i),
                                emb_opt, dense_opt)
    es = emb_opt.init({"arenas": params["arenas"]})
    ds = dense_opt.init({k: params[k] for k in model.cfg.dense_keys})
    names = list(op_names(jax.jit(step).lower(
        params, es, ds, batch).compile().as_text()).values())
    for i in range(model.cfg.cross_layers):
        under = [n for n in names if dlrm.CROSS_SCOPE.format(i)
                 in scopes_of(n)]
        assert any("transpose(" in n for n in under), i
        assert any("transpose(" not in n for n in under), i
        assert all(dlrm.INTERACT_SCOPE in scopes_of(n) for n in under)
    for scope in (E.BWD_SORT_SCOPE, E.BWD_FETCH_SCOPE, E.UPDATE_ROWS_SCOPE):
        under = [n for n in names if scope in scopes_of(n)]
        assert under, scope
        assert all(dlrm.EMB_UPDATE_SCOPE in scopes_of(n) for n in under)

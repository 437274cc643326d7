"""One placed DLRM train step against the plain float32 reference.

The step is the main path from a placement to the chip's program:
``expert_place`` -> ``build_plan`` -> ``DLRM`` (dot interaction) ->
``make_train_step`` on ``lookup_unsharded``.  Its loss, its updated
arenas (the rows the batch touched, and every other row, which must come
back bit for bit with a zero accumulator) and its updated dense
parameters are compared with ``dlrm_reference`` (f32, HIGHEST precision,
autodiff, row-wise Adagrad on every row) over the grid

  pool {dim-16 DLRM, mixed-dim production padded to its widest (48)}
  x shards {1, 2, 4} x index layout {padded (B, S*K, P), bag widths}
  x embedding update {``rowwise_adagrad`` on the dense arena gradient,
                      ``RowWiseAdagrad`` on the touched rows}
  x arena dtype {f32, bf16},

at 8 tables of 1,000-4,000 rows, batch 32, bags up to 8 ids with one
sample's bags empty and a hot row in table 0.  The dense side is f32 and
takes plain SGD: Adam's first step is ``lr * sign(g)`` and would check
only the gradients' signs.

How the tolerances were set (``GAPS``).  Each gap is relative: the loss
to the reference loss; the touched rows' values to the largest change
the reference makes to a row (about ``LR``); the accumulators to the
largest reference accumulator; each dense leaf to its largest reference
change.  Two reruns of the reference gave each gap's scale over the
whole grid (48 cases, CPU backend): an f32 rerun on the batch in
reverse sample order, which sums every gradient in another order, and
a bf16 rerun, whose arena gradient, accumulators and new rows are
rounded to bf16 (it keeps the reference's loss and dense side).  Worst
gaps over the grid:

  gap     f32 rerun  bf16 rerun  step, f32 arenas  step, bf16 arenas
  loss    3.27e-07   --          1.93e-07          1.87e-07
  rows    4.42e-07   7.12e-03    1.86e-06          1.24e-02
  acc     4.84e-07   5.02e-03    6.53e-07          4.60e-03
  dense   1.14e-05   --          2.25e-05          9.50e-06

Each limit is ten times the worst matching rerun, rounded up to two
digits: the loss and the dense side (f32 in every case) and the f32
arenas take the f32 rerun's, the bf16 arenas the bf16 rerun's.  The
step's bf16 rows sit above the bf16 rerun because the dense-gradient
update keeps its accumulators and adds its update in bf16 too (the
touched-row update reads 3.5e-03: its new rows alone are rounded).  A
missed or doubled slot moves a row by a large part of its change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlrm_reference as R
from repro.core import baselines as B
from repro.core import features as F
from repro.embedding import sharded as E
from repro.embedding.plan import build_plan
from repro.models import dlrm
from repro.optim import RowWiseAdagrad, rowwise_adagrad, sgd

LR, EPS, DENSE_LR = 0.05, 1e-8, 0.1
M, BATCH, P = 8, 32, 8
ROWS = (1_000, 4_000)            # rows a table, clipped to this range
HOT = 3                          # table 0's hot row
GAPS = {jnp.float32: {"loss": 3.3e-6, "rows": 4.5e-6, "acc": 4.9e-6,
                      "dense": 1.2e-4},
        jnp.bfloat16: {"loss": 3.3e-6, "rows": 0.072, "acc": 0.051,
                       "dense": 1.2e-4}}


def placed(pool, n_shards, layout, update):
    """(raw, widths, plan) for the first M tables of ``pool``."""
    raw = pool[:M].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], *ROWS)
    raw[:, F.TABLE_SIZE_GB] = F.table_size_gb(raw[:, F.DIM],
                                              raw[:, F.HASH_SIZE])
    widths = np.minimum(raw[:, F.POOLING].astype(np.int64) + 1, P)
    assign = B.expert_place(raw, n_shards, 1e9, "size")
    plan = build_plan(raw, assign, n_shards, pad_dim_to=16,
                      widths=widths if layout == "widths" else None,
                      pad_rows_to=16 if update == "rows" else 1)
    return raw, widths, plan


def make_ids(raw, widths, layout, seed):
    """(BATCH, M, P) ids, -1 padded: bags of 0..P ids (padded layout) or
    of exactly the table's width (bag widths); sample 0's bags empty and
    a quarter of table 0's ids on its hot row."""
    rng = np.random.default_rng(seed)
    ids = np.full((BATCH, M, P), -1, np.int32)
    for t in range(M):
        for b in range(1, BATCH):
            n = rng.integers(0, P + 1) if layout == "padded" else widths[t]
            ids[b, t, :n] = rng.integers(0, raw[t, F.HASH_SIZE], n)
    hot = (rng.random((BATCH, P)) < 0.25) & (ids[:, 0] >= 0)
    ids[:, 0] = np.where(hot, HOT, ids[:, 0])
    return ids


def setup(pool, n_shards, layout, update, dtype, seed=0):
    raw, widths, plan = placed(pool, n_shards, layout, update)
    model = dlrm.DLRM(dlrm.DLRMConfig(n_dense_features=4, embed_dim=plan.dim,
                                      bottom_mlp=(16,), top_mlp=(16,),
                                      n_tables=M), plan)
    params = model.init_params(jax.random.PRNGKey(seed))
    params["arenas"] = params["arenas"].astype(dtype)
    ids = make_ids(raw, widths, layout, seed)
    rng = np.random.default_rng(seed + 1)
    batch = {"dense": jnp.asarray(rng.normal(size=(BATCH, 4)), jnp.float32),
             "gidx": jnp.asarray(E.group_indices(plan, ids)),
             "labels": jnp.asarray(rng.integers(0, 2, BATCH), jnp.float32)}
    return model, params, ids, batch


def run_step(model, params, batch, update):
    plan = model.plan
    emb_opt = (RowWiseAdagrad(LR, eps=EPS) if update == "rows"
               else rowwise_adagrad(LR, eps=EPS))
    dense_opt = sgd(DENSE_LR)
    step = dlrm.make_train_step(
        model, lambda a, b, i: E.lookup_unsharded(a, b, i, plan), emb_opt,
        dense_opt)
    es = emb_opt.init({"arenas": params["arenas"]})
    ds = dense_opt.init({k: params[k] for k in model.cfg.dense_keys})
    with jax.default_matmul_precision("highest"):
        new, es, _, loss = jax.jit(step)(params, es, ds, batch)
    return new, es.inner["arenas"], loss


def reference(params, plan, ids, batch):
    """(loss, new arenas, accumulators, new dense params), all f32."""
    loss, gd, ga = R.loss_and_grads(params, plan, jnp.asarray(ids),
                                    batch["dense"], batch["labels"], "dot")
    arenas, acc = R.adagrad_step(params["arenas"].astype(jnp.float32),
                                 jnp.zeros(ga.shape[:2]), ga, LR, EPS)
    dense = jax.tree.map(lambda p, g: p - DENSE_LR * g,
                         {k: params[k] for k in gd}, gd)
    return loss, arenas, acc, dense


def touched_rows(plan, ids):
    """(S, R) bool: the arena rows the batch's live ids address."""
    out = np.zeros((plan.n_shards, plan.rows_max), bool)
    for s in range(plan.n_shards):
        for k in range(plan.k_max):
            t = plan.slot_table[s, k]
            if t >= 0:
                live = ids[:, t][ids[:, t] >= 0]
                out[s, plan.base_rows[s, k] + live] = True
    return out


def gaps(got, want, old, touched):
    """The relative gaps the docstring defines, from (loss, arenas, acc,
    dense) of a run and of the reference, and the step's input."""
    loss, arenas, acc, dense = got
    w_loss, w_arenas, w_acc, w_dense = want
    def f64(a):
        return np.asarray(a, np.float64)

    change = np.abs(f64(w_arenas) - f64(old["arenas"]))[touched].max()
    return {
        "loss": abs(float(loss) - float(w_loss)) / abs(float(w_loss)),
        "rows": np.abs(f64(arenas) - f64(w_arenas))[touched].max() / change,
        "acc": np.abs(f64(acc) - f64(w_acc)).max() / f64(w_acc).max(),
        "dense": max(
            np.abs(f64(a) - f64(w)).max() / np.abs(f64(w) - f64(o)).max()
            for a, w, o in zip(jax.tree.leaves(dense),
                               jax.tree.leaves(w_dense),
                               jax.tree.leaves({k: old[k]
                                                for k in w_dense})))}


@pytest.fixture(scope="module", params=["dlrm", "prod"])
def pool(request, dlrm_pool, prod_pool):
    return dlrm_pool if request.param == "dlrm" else prod_pool


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("update", ["dense", "rows"])
@pytest.mark.parametrize("layout", ["padded", "widths"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_placed_step_matches_reference(pool, n_shards, layout, update,
                                       dtype):
    model, params, ids, batch = setup(pool, n_shards, layout, update, dtype)
    new, acc, loss = run_step(model, params, batch, update)
    want = reference(params, model.plan, ids, batch)
    touched = touched_rows(model.plan, ids)
    assert touched[:, 0].sum() == 0            # row 0 is never addressed

    got = gaps((loss, new["arenas"], acc,
                {k: new[k] for k in model.cfg.dense_keys}),
               want, params, touched)
    limits = GAPS[dtype]
    for name, gap in got.items():
        assert gap <= limits[name], (name, gap, limits[name])

    # every row the batch did not touch comes back bit for bit
    old = np.asarray(params["arenas"].astype(jnp.float32))
    out = np.asarray(new["arenas"].astype(jnp.float32))
    assert new["arenas"].dtype == params["arenas"].dtype
    np.testing.assert_array_equal(out[~touched], old[~touched])
    assert (np.asarray(acc, np.float32)[~touched] == 0).all()
    assert (np.asarray(acc, np.float32)[touched] > 0).all()

"""The column loop of the placed lookup (``embedding.sharded._lookup``).

A ``(B, K, P)`` shard lookup gathers only the (slot, position) index
columns that hold a live id somewhere in the batch: one loop over the
live columns, each a gather of B rows added into its slot's bag.
Checked against the per-row formula (``per_row``: XLA's gather of single
rows and a masked f32 sum) at D 16 (row groups) and D 128 (single rows)
on ragged, all-padding, all-live, single-sample and dead-slot batches,
through ``lookup_unsharded`` and the sharded lookup on four CPU devices;
the gradients through the custom VJP; a program whose size does not grow
with the pooling P; and the ``emb.lookup.column_loop`` counter on the
benchmark's two plans.

Arena and cotangent values lie on a 2**-6 grid, so every f32 sum is exact
in any order and the comparisons are bitwise."""

import collections
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.embedding import sharded as E
from repro.embedding.plan import build_plan
from test_lane_grouped_lookup import _primitives, grid_arena, per_row

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CASES = ("ragged", "all_padding", "all_live", "single_sample", "dead_slot")
B, K, P = 12, 4, 6


def case_ids(case: str, n_rows: int, seed: int = 0, pool: int = P):
    """(B, K, ``pool``) ids of one case within K tables after row 0, and
    the tables' bases."""
    rng = np.random.default_rng(seed)
    per = (n_rows - 1) // K
    idx = rng.integers(0, per, (B, K, pool))
    idx[:, :, -1] = per - 1
    if case in ("ragged", "dead_slot"):
        # each slot's bag length, as the traffic pads it: positions past
        # it are padding in every sample; and ragged holes inside it
        length = rng.integers(1, pool + 1, K)
        idx = np.where(np.arange(pool) < length[:, None], idx, -1)
        idx = np.where(rng.random(idx.shape) < 0.3, -1, idx)
        idx[1] = -1
    if case == "dead_slot":
        idx[:, 2] = -1
    if case == "all_padding":
        idx[:] = -1
    if case == "single_sample":
        keep = idx[5, 1, 3]
        idx[:] = -1
        idx[5, 1, 3] = keep
    return (jnp.asarray(idx, jnp.int32),
            1 + jnp.arange(K, dtype=jnp.int32) * per)


def _n_rows(dim):
    return 40 * max(1, 128 // dim) + 3


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dim", [16, 128])
def test_column_loop_equals_per_row_sums(dim, case):
    arena = grid_arena(_n_rows(dim), dim, seed=dim)
    idx, bases = case_ids(case, _n_rows(dim), seed=dim)
    got = jax.jit(E._local_lookup)(arena, bases, idx)
    want = per_row(arena, bases, idx)
    assert got.shape == (B, K, dim) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    live = np.asarray(idx >= 0).any(-1)
    assert not np.asarray(got)[~live].any()
    assert (float(jnp.abs(want).max()) > 0) == (case != "all_padding")


def _cotangent(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).integers(-64, 65, shape)
                       / 64.0, jnp.float32)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dim", [16, 128])
def test_column_loop_gradient_is_the_per_row_one(dim, case):
    """The custom VJP (the sorted backward) gives the per-row gather's
    autodiff gradient, bit for bit on the grid, and row 0 none."""
    arena = grid_arena(_n_rows(dim), dim, seed=3, dtype=jnp.float32)
    idx, bases = case_ids(case, _n_rows(dim), seed=3)
    cot = _cotangent((B, K, dim), 4)

    def grad(f):
        return jax.jit(jax.grad(
            lambda a: jnp.sum(f(a, bases, idx) * cot)))(arena)

    got, want = grad(E._local_lookup), grad(per_row)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[0]).any()


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path[:0] = sys.argv[1:3]
dim = int(sys.argv[3])
import numpy as np, jax, jax.numpy as jnp
from repro.embedding import sharded as E
from test_column_skip_lookup import CASES, _cotangent, sharded_case
from test_lane_grouped_lookup import per_row

for case in CASES:
    plan, arenas, gidx = sharded_case(case, dim)
    mesh = jax.make_mesh((1, plan.n_shards), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    lookup = E.make_sharded_lookup(mesh, plan)
    bases = jnp.asarray(plan.base_rows)
    cot = _cotangent((gidx.shape[0], gidx.shape[1], plan.dim), 6)

    def reference(a):
        return jnp.concatenate(
            [per_row(a[s], bases[s], E.shard_indices(plan, gidx, s))
             for s in range(plan.n_shards)], axis=1)

    with jax.set_mesh(mesh):
        got = jax.jit(lambda a: lookup(a, bases, gidx))(arenas)
        d_got = jax.jit(jax.grad(
            lambda a: jnp.sum(lookup(a, bases, gidx) * cot)))(arenas)
    d_want = jax.grad(lambda a: jnp.sum(reference(a) * cot))(arenas)
    want = reference(arenas)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(d_got), np.asarray(d_want))
    print("SHARDED_OK", case)
"""


def sharded_case(case: str, dim: int):
    """(plan of 8 tables on 4 shards, f32 grid arenas, grouped ids) of
    one case, 16 samples of bags up to 4."""
    from repro.data.synthetic import make_dlrm_pool
    raw = make_dlrm_pool(seed=0)[:8].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 300)
    raw[:, F.DIM] = 16
    plan = build_plan(raw, np.arange(8) % 4, 4, pad_dim_to=dim)
    arenas = jnp.stack([grid_arena(plan.rows_max, plan.dim, seed=s,
                                   dtype=jnp.float32) for s in range(4)])
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 250, (16, 8, 4))
    if case in ("ragged", "dead_slot"):
        length = rng.integers(1, 5, 8)
        ids = np.where(np.arange(4) < length[:, None], ids, -1)
        ids = np.where(rng.random(ids.shape) < 0.3, -1, ids)
    if case == "dead_slot":
        ids[:, 5] = -1
    if case == "all_padding":
        ids[:] = -1
    if case == "single_sample":
        keep = ids[9, 2, 1]
        ids[:] = -1
        ids[9, 2, 1] = keep
    gidx = jnp.asarray(E.group_indices(plan, ids.astype(np.int32)))
    return plan, arenas, gidx


@pytest.mark.parametrize("dim", [16, 128])
def test_sharded_column_loop_equals_per_row_4dev(dim):
    """``make_sharded_lookup`` (the column loop under ``shard_map``, then
    the all-to-all) on four CPU devices: values and gradients of every
    case equal the per-row formula's."""
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT,
                        os.path.join(ROOT, "src"), here, str(dim)],
                       capture_output=True, text=True, timeout=600)
    ok = [line.split()[1] for line in r.stdout.splitlines()
          if line.startswith("SHARDED_OK")]
    assert ok == list(CASES), r.stdout + r.stderr


@pytest.mark.parametrize("dim", [16, 128])
def test_program_does_not_grow_with_the_pooling(dim):
    """The lookup's program at P = 4 and P = 16 has the same equations
    (one gather, in one loop over the live columns) and the same
    StableHLO ops: no per-column code."""
    arena = grid_arena(_n_rows(dim), dim, seed=0)

    def program(pool):
        idx, bases = case_ids("ragged", _n_rows(dim), pool=pool)
        jaxpr = jax.make_jaxpr(E._local_lookup)(arena, bases, idx).jaxpr
        hlo = jax.jit(E._local_lookup).lower(arena, bases, idx).as_text()
        ops = collections.Counter(
            re.findall(r"(?<!#)\bstablehlo\.(\w+)", hlo))
        return _primitives(jaxpr), ops

    (small, small_ops), (large, large_ops) = program(4), program(16)
    assert small == large
    assert small_ops == large_ops
    assert small["gather"] == 1 and small["while"] == 1
    assert small_ops["gather"] == 1 and small_ops["while"] == 1


def cell_plan(name: str):
    """The plan a benchmark configuration's step builds."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import program
    from bench.pool import make_pool
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        c = json.load(f)
    if name == "dlrm50":
        pool, _ = make_pool(c["pool"]["n_tables"], c["pool"]["seed"])
        raw = pool[:c["n_tables"]]
        return build_plan(raw, program.place(raw, c), c["n_shards"],
                          pad_dim_to=c["embed_dim"])
    rows = np.asarray(c["num_embeddings_per_feature"], np.float64)
    raw = np.zeros((rows.shape[0], F.NUM_FEATURES))
    raw[:, F.DIM], raw[:, F.HASH_SIZE] = c["embedding_dim"], rows
    return build_plan(raw, np.zeros(rows.shape[0], int), 1,
                      pad_dim_to=c["embedding_dim"],
                      widths=c["multi_hot_sizes"],
                      pad_rows_to=c["pad_rows_to"])


@pytest.mark.parametrize("name,count", [("dlrm50", 4), ("dlrm_dcnv2", 0)])
def test_column_loop_counter_counts_shard_lookups(telemetry, name, count):
    """Traced at the cells' shapes (nothing runs): one count a shard
    lookup on the column loop, as many as on the row-group gather; none
    for the bag-width plan."""
    plan = cell_plan(name)
    cols = (plan.n_shards * plan.k_max, 16) if plan.col_slot is None else (
        plan.n_shards * plan.n_cols,)
    jax.eval_shape(
        lambda a, i: E.lookup_unsharded(a, plan.base_rows, i, plan),
        jax.ShapeDtypeStruct((plan.n_shards, plan.rows_max, plan.dim),
                             jnp.bfloat16),
        jax.ShapeDtypeStruct((64, *cols), jnp.int32))
    assert telemetry.counter_value(E.COLUMN_LOOP_COUNTER) == count
    assert telemetry.counter_value(E.LANE_GROUPED_COUNTER) == count

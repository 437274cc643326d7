"""The lane-grouped forward of the placed lookup (``embedding.sharded``).

An arena narrower than the 128 lanes, whose width divides them, is
gathered by 128-lane row groups (``_row_groups`` -> one gather a live
index column -> each slot's own lanes kept -> bag sums -> lane groups
folded); any other width gathers single rows of the arena.  Checked against the
per-row formula (XLA's gather of single rows and a masked f32 sum): the
plain-JAX regroup and the Pallas kernel in interpret mode, plans of one
and four shards, the column layout, the custom VJP's gradients and the
``emb.lookup.lane_grouped`` counter.

Arena values lie on a 2**-6 grid, so every f32 sum of a bag is exact and
the two summation orders (over the slots, or over each lane group's
slots and then the groups) give the same bits; a wrong lane or row kept
or dropped changes them."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.data.synthetic import make_dlrm_pool
from repro.embedding import sharded as E
from repro.embedding.plan import build_plan
from repro.kernels.embedding_bag import regroup as G


def per_row(arena, bases, idx):
    """The lookup as XLA's gather of single rows and a masked f32 sum."""
    live = idx >= 0
    rows = jnp.take(arena, jnp.where(live, idx + bases[None, :, None], 0),
                    axis=0)
    return jnp.where(live[..., None], rows, 0).astype(jnp.float32).sum(2)


def per_row_cols(arena, bases, idx, col_slot):
    """The column lookup as a gather of single rows and static sums."""
    slot = np.maximum(np.asarray(col_slot), 0)
    rows = jnp.take(arena, jnp.where(idx >= 0, idx + bases[slot][None, :],
                                     0), axis=0)
    rows = jnp.where((idx >= 0)[..., None], rows, 0).astype(jnp.float32)
    return E._pool_cols(rows, tuple(col_slot), bases.shape[0])


def grid_arena(n_rows, dim, seed, dtype=jnp.bfloat16):
    """Values k / 64, |k| <= 64, row 0 non-zero (padded slots point at
    it and must still add nothing)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-64, 65, (n_rows, dim)) / 64.0
    a[0] = 1.0
    return jnp.asarray(a, dtype)


def shard_ids(n_rows, k, batch, pool, seed):
    """(B, K, P) ids within K tables of ``(n_rows - 1) // k`` rows after
    the reserved row 0: a third padding, a sample and a bag of padding
    only, one hot row in a quarter of the slots, the table's last row in
    some."""
    rng = np.random.default_rng(seed)
    per = (n_rows - 1) // k
    idx = rng.integers(0, per, (batch, k, pool))
    idx = np.where(rng.random(idx.shape) < 0.25, 3, idx)
    idx[:, :, -1] = per - 1
    idx = np.where(rng.random(idx.shape) < 0.33, -1, idx)
    idx[1] = -1
    idx[2, 0] = -1
    return (jnp.asarray(idx, jnp.int32),
            1 + jnp.arange(k, dtype=jnp.int32) * per)


@pytest.fixture()
def kernel_path(monkeypatch):
    """The TPU branch of the regroup, its Pallas kernel in interpret
    mode."""
    def tpu_branch(*args, tpu, default):
        return tpu(*args)

    monkeypatch.setattr(E.jax.lax, "platform_dependent", tpu_branch)
    monkeypatch.setattr(G, "row_groups", functools.partial(
        G.row_groups, interpret=True))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("dim", [1, 2, 4, 8, 16, 32, 64])
def test_regroup_kernel_matches_plain_form(dim, dtype):
    """Two whole blocks and a tail that is not a multiple of g."""
    n_rows = 2 * G.BLOCK_ROWS + 77
    arena = jax.random.normal(jax.random.PRNGKey(dim), (n_rows, dim),
                              jnp.float32).astype(dtype)
    got = G.row_groups(arena.T, interpret=True)
    want = G.row_groups_ref(arena)
    assert got.shape == (-(-n_rows // (128 // dim)), 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the tail group's rows past the arena are zero
    assert not np.asarray(got[-1, (n_rows % (128 // dim)) * dim:]).any()


@pytest.mark.parametrize("path", ["plain", "kernel"])
@pytest.mark.parametrize("dim", [16, 32, 64])
def test_grouped_lookup_equals_per_row_sums(request, dim, path):
    if path == "kernel":
        request.getfixturevalue("kernel_path")
    g = 128 // dim
    n_rows = 40 * g + 3                       # not a multiple of g
    arena = grid_arena(n_rows, dim, seed=dim)
    idx, bases = shard_ids(n_rows, 4, 12, 6, seed=dim)
    got = jax.jit(E._local_lookup)(arena, bases, idx)
    want = per_row(arena, bases, idx)
    assert got.shape == (12, 4, dim) and got.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[1]).any() and not np.asarray(got[2, 0]).any()


def _gathers(jaxpr):
    """Every gather's (operand shape, slice sizes) in a jaxpr, nested
    jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((eqn.invars[0].aval.shape, eqn.params["slice_sizes"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _gathers(sub)
    return out


def test_grouped_lookup_is_one_gather_of_row_groups():
    arena = grid_arena(1001, 16, seed=0)
    idx, bases = shard_ids(1001, 4, 8, 5, seed=0)
    jaxpr = jax.make_jaxpr(E._local_lookup)(arena, bases, idx).jaxpr
    assert _gathers(jaxpr) == [((126, 128), (1, 128))]


def _body(closed_jaxpr):
    """A jaxpr's equations as text, without its binders (which list
    closed-over constants apart from arguments)."""
    return str(closed_jaxpr.jaxpr).split("let", 1)[1]


def _primitives(jaxpr) -> collections.Counter:
    """Primitive name -> count in a jaxpr, nested jaxprs included."""
    out = collections.Counter()
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _primitives(sub)
    return out


@pytest.mark.parametrize("dim", [128, 48])
def test_wide_or_non_dividing_dims_keep_the_per_row_path(telemetry, dim):
    """At D = 128, or a D that does not divide 128, the lookup gathers
    single rows of the arena itself: the column loop's one gather takes
    (1, D) rows of the (R, D) arena, with no regroup, and nothing counts
    as lane-grouped.  The column lookup's program is the per-row formula,
    equation for equation."""
    arena = grid_arena(301, dim, seed=1)
    idx, bases = shard_ids(301, 3, 8, 5, seed=1)
    jaxpr = jax.make_jaxpr(E._local_lookup)(arena, bases, idx).jaxpr
    assert _gathers(jaxpr) == [((301, dim), (1, dim))]
    assert not {"platform_index", "pallas_call"} & set(_primitives(jaxpr))
    col_slot = (0, 0, 1, -1, 2, 2)
    cidx = idx[:, :, 0].repeat(2, axis=1)
    (eqn,) = jax.make_jaxpr(functools.partial(
        E._local_lookup, col_slot=col_slot))(arena, bases, cidx).jaxpr.eqns
    assert _body(eqn.params["call_jaxpr"]) == _body(jax.make_jaxpr(
        functools.partial(per_row_cols, col_slot=col_slot))(
            arena, bases, cidx))
    assert telemetry.counter_value(E.LANE_GROUPED_COUNTER) == 0


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_grouped_column_lookup_equals_per_row_sums(request, path):
    """Bag widths at D = 16: slot 0 two columns, slot 1 none, slot 2
    three, a padding column between."""
    if path == "kernel":
        request.getfixturevalue("kernel_path")
    arena = grid_arena(803, 16, seed=2)
    idx, bases = shard_ids(803, 3, 10, 6, seed=2)
    col_slot = (0, 0, -1, 2, 2, 2)
    cidx = idx.reshape(10, -1)[:, :6]
    got = E._local_lookup(arena, bases, cidx, col_slot)
    want = per_row_cols(arena, bases, cidx, col_slot)
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[:, 1]).any()


@pytest.mark.parametrize("dim", [16, 32, 64])
def test_grouped_lookup_gradient_is_the_per_row_one(dim):
    """The custom VJP is untouched by the grouped forward: the arena
    gradient is the per-row gather's autodiff transpose (a scatter-add,
    the same f32 terms in another order) and row 0 gets none."""
    n_rows = 30 * (128 // dim) + 5
    arena = grid_arena(n_rows, dim, seed=3, dtype=jnp.float32)
    idx, bases = shard_ids(n_rows, 4, 16, 5, seed=3)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=(16, 4, dim)),
                      jnp.float32)

    def grad(f):
        return jax.grad(lambda a: jnp.sum(f(a, bases, idx) * cot))(arena)

    got, want = grad(E._local_lookup), grad(per_row)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert not np.asarray(got[0]).any()


@pytest.mark.parametrize("dim,count", [(16, 4), (128, 0)])
def test_lane_grouped_counter_counts_shard_lookups(telemetry, dim, count):
    """``lookup_unsharded`` on a plan of four shards: one count a shard
    lookup on the grouped path, and its sums are the per-row ones."""
    raw = make_dlrm_pool(seed=0)[:8].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 300)
    raw[:, F.DIM] = 16
    plan = build_plan(raw, np.arange(8) % 4, 4, pad_dim_to=dim)
    arenas = jnp.stack([grid_arena(plan.rows_max, plan.dim, seed=s)
                        for s in range(4)])
    rng = np.random.default_rng(5)
    ids = np.where(rng.random((8, 8, 4)) < 0.3, -1,
                   rng.integers(0, 250, (8, 8, 4))).astype(np.int32)
    gidx = jnp.asarray(E.group_indices(plan, ids))
    got = E.lookup_unsharded(arenas, plan.base_rows, gidx, plan)
    assert telemetry.counter_value(E.LANE_GROUPED_COUNTER) == count
    want = jnp.concatenate(
        [per_row(arenas[s], jnp.asarray(plan.base_rows[s]),
                 E.shard_indices(plan, gidx, s)) for s in range(4)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

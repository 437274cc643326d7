"""The lookup's backward (``embedding.sharded._lookup_bwd``): slots sorted
by arena row, their gradients summed in f32, padding sent past the end.

Checked against the autodiff transpose of the plain gather on f32 arenas,
through ``lookup_unsharded`` and through ``make_sharded_lookup`` on four
CPU devices; a bf16 arena row hit 4096 times; and the Pallas kernel in
interpret mode against its plain-JAX form."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.data.synthetic import make_dlrm_pool
from repro.embedding import sharded as E
from repro.embedding.plan import build_plan
from repro.kernels.embedding_bag import backward as K

CASES = ("uniform", "bag_of_padding", "one_row", "padded_shards")


def gather_lookup(arena, bases, idx):
    """The lookup as XLA's gather and a masked sum, differentiated by
    autodiff (its transpose is a scatter-add)."""
    live = idx >= 0
    rows = jnp.take(arena, jnp.where(live, idx + bases[None, :, None], 0),
                    axis=0)
    return jnp.where(live[..., None], rows, 0).astype(jnp.float32).sum(2)


def case_inputs(case: str, seed: int = 0):
    """(plan, grouped indices (B, S*K, P)) of one case, 16 rows x 5 slots."""
    pool = make_dlrm_pool(seed=0)
    M, S = (7, 4) if case == "padded_shards" else (8, 4)
    raw = pool[:M].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 500)
    assign = (np.array([0, 0, 0, 1, 2, 3, 3]) if case == "padded_shards"
              else np.arange(M) % S)
    plan = build_plan(raw, assign, S)
    rng = np.random.default_rng(seed)
    B, P = 16, 5
    idx = np.where(rng.random((B, M, P)) < 0.2, -1,
                   rng.integers(0, 400, (B, M, P))).astype(np.int32)
    if case == "bag_of_padding":
        idx[3, 2] = -1                      # one bag, every slot padding
        idx[7] = -1                         # one sample with no lookups
    if case == "one_row":
        idx = np.where(idx >= 0, 17, -1).astype(np.int32)
    return plan, E.group_indices(plan, idx)


def assert_sums_close(got, want):
    """f32 sums of the same terms in another order: within a few ulps of
    the largest sum."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(np.abs(want).max()))


def grads_of(lookup, arenas, gidx, seed: int = 1):
    cot = np.random.default_rng(seed).normal(
        size=(gidx.shape[0], gidx.shape[1], arenas.shape[-1]))
    return jax.grad(lambda a: jnp.sum(lookup(a, gidx) * cot))(arenas)


@pytest.mark.parametrize("case", CASES)
def test_backward_matches_gather_transpose(case):
    plan, gidx = case_inputs(case)
    arenas = E.init_arenas(jax.random.PRNGKey(0), plan)

    def ours(a, i):
        return E.lookup_unsharded(a, plan.base_rows, i, plan)

    def reference(a, i):
        return jnp.concatenate(
            [gather_lookup(a[s], jnp.asarray(plan.base_rows[s]),
                           i[:, s * plan.k_max:(s + 1) * plan.k_max])
             for s in range(plan.n_shards)], axis=1)

    got = grads_of(ours, arenas, jnp.asarray(gidx))
    want = grads_of(reference, arenas, jnp.asarray(gidx))
    assert float(jnp.abs(want).max()) > 0
    assert_sums_close(got, want)
    np.testing.assert_array_equal(np.asarray(got)[:, 0], 0.0)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path[:0] = sys.argv[1:3]
import numpy as np, jax, jax.numpy as jnp
from repro.embedding import sharded as E
from test_embedding_backward import (CASES, assert_sums_close, case_inputs,
                                     gather_lookup, grads_of)

for case in CASES:
    plan, gidx = case_inputs(case)
    arenas = E.init_arenas(jax.random.PRNGKey(0), plan)
    mesh = jax.make_mesh((1, plan.n_shards), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    lookup = E.make_sharded_lookup(mesh, plan)
    bases = jnp.asarray(plan.base_rows)

    def reference(a, i):
        return jnp.concatenate(
            [gather_lookup(a[s], bases[s],
                           i[:, s * plan.k_max:(s + 1) * plan.k_max])
             for s in range(plan.n_shards)], axis=1)

    with jax.set_mesh(mesh):
        got = grads_of(lambda a, i: lookup(a, bases, i), arenas,
                       jnp.asarray(gidx))
    want = grads_of(reference, arenas, jnp.asarray(gidx))
    assert_sums_close(got, want)
    print("SHARDED_OK", case)
"""


def test_sharded_backward_matches_gather_transpose_4dev():
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT,
                        os.path.join(here, "..", "src"), here],
                       capture_output=True, text=True, timeout=600)
    ok = [line.split()[1] for line in r.stdout.splitlines()
          if line.startswith("SHARDED_OK")]
    assert ok == list(CASES), r.stdout + r.stderr


def test_bf16_hot_row_gets_every_contribution():
    """A bf16 row hit 4096 times gets 4096 g: the sum is f32 until it is
    written, where a bf16 scatter-add stops adding near 2**9 g."""
    B, P, g = 256, 16, 0.375
    arena = jnp.zeros((64, 16), jnp.bfloat16)
    idx = jnp.full((B, 1, P), 5, jnp.int32)
    d_arena = jax.grad(lambda a: jnp.sum(
        E._local_lookup(a, jnp.zeros((1,), jnp.int32), idx)) * g)(arena)
    assert d_arena.dtype == jnp.bfloat16
    want = np.zeros((64, 16), np.float32)
    want[5] = B * P * g
    np.testing.assert_array_equal(np.asarray(d_arena, np.float32), want)


def _sorted_keys(rng, n_rows, n, law):
    if law == "uniform":
        keys = rng.integers(0, n_rows, n)
    elif law == "hot":                       # half the slots on two rows
        keys = np.where(rng.random(n) < 0.5, rng.choice([3, n_rows - 1], n),
                        rng.integers(0, n_rows, n))
    else:                                    # every slot padding
        keys = np.full(n, n_rows)
    keys[rng.random(n) < 0.3] = n_rows       # padded slots
    return np.sort(keys).astype(np.int32)


@pytest.mark.parametrize("law", ["uniform", "hot", "padding"])
@pytest.mark.parametrize("dim", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_interpret_matches_plain_form(law, dim, dtype):
    rng = np.random.default_rng(dim)
    n_rows, n = 4100, 3 * K.CHUNK            # a partial last block
    keys = jnp.asarray(_sorted_keys(rng, n_rows, n, law))
    grads = jnp.asarray(rng.normal(size=(n, dim)), jnp.float32)
    got = K.sorted_row_sum(keys, grads, n_rows=n_rows, dtype=dtype,
                           interpret=True)
    want = K.sorted_row_sum_ref(keys, grads, n_rows=n_rows, dtype=dtype)
    assert got.shape == want.shape == (n_rows, dim) and got.dtype == dtype
    if dtype == jnp.float32:
        assert_sums_close(got, want)
    else:                                    # one rounding of close sums
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2.0 ** -8, atol=1e-6)

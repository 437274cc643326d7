"""DLRM + distributed embedding substrate: plan grouping, oracle lookup,
sharded==oracle equality (subprocess with fake devices), gradient flow."""

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
from repro.models.dlrm import DLRM, DLRMConfig


@pytest.fixture(scope="module")
def setup():
    pool = make_dlrm_pool(seed=0)
    M, S = 8, 4
    raw = pool[:M].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 500)
    assign = np.arange(M) % S
    plan = build_plan(raw, assign, S)
    cfg = DLRMConfig(n_dense_features=4, embed_dim=plan.dim,
                     bottom_mlp=(32,), top_mlp=(64, 32), n_tables=M)
    model = DLRM(cfg, plan)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, P = 16, 5
    idx = np.where(rng.random((B, M, P)) < 0.2, -1,
                   rng.integers(0, 400, (B, M, P))).astype(np.int32)
    return model, params, plan, raw, idx, rng


def _oracle(plan):
    return lambda a, b, i: E.lookup_unsharded(a, plan.base_rows, i, plan)


def test_group_indices_roundtrip(setup):
    model, params, plan, raw, idx, rng = setup
    gidx = E.group_indices(plan, idx)
    assert gidx.shape == (idx.shape[0], plan.n_shards * plan.k_max,
                          idx.shape[2])
    order = plan.grouped_index_order()
    for slot, table in enumerate(order):
        if table >= 0:
            np.testing.assert_array_equal(gidx[:, slot], idx[:, table])
        else:
            assert (gidx[:, slot] == -1).all()


def test_forward_finite(setup):
    model, params, plan, raw, idx, rng = setup
    gidx = jnp.asarray(E.group_indices(plan, idx))
    dense = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    logits = model.forward(params, dense, gidx, _oracle(plan))
    assert logits.shape == (16,)
    assert np.isfinite(np.asarray(logits)).all()


def test_gradients_reach_arenas(setup):
    model, params, plan, raw, idx, rng = setup
    gidx = jnp.asarray(E.group_indices(plan, idx))
    dense = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, 16), jnp.float32)

    def loss(p):
        return DLRM.loss(model.forward(p, dense, gidx, _oracle(plan)), labels)

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["arenas"]).max()) > 0
    assert float(jnp.abs(g["bottom"][0]["w"]).max()) > 0
    # zero rows receive no gradient weight updates beyond scatter artifacts
    assert np.isfinite(np.asarray(g["arenas"])).all()


def test_bce_loss_bounds():
    logits = jnp.asarray([-5.0, 0.0, 5.0])
    labels = jnp.asarray([0.0, 1.0, 1.0])
    loss = float(DLRM.loss(logits, labels))
    assert 0 < loss < 1.0


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, sys.argv[1])
import numpy as np, jax, jax.numpy as jnp
from repro.core import features as F
from repro.data.synthetic import make_dlrm_pool
from repro.embedding.plan import build_plan
from repro.embedding import sharded as E

pool = make_dlrm_pool(seed=0)
M, S = 8, 4
raw = pool[:M].copy()
raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 500)
plan = build_plan(raw, np.arange(M) % S, S)
arenas = E.init_arenas(jax.random.PRNGKey(0), plan)
rng = np.random.default_rng(0)
B, P = 16, 5
idx = np.where(rng.random((B, M, P)) < 0.2, -1,
               rng.integers(0, 400, (B, M, P))).astype(np.int32)
gidx = jnp.asarray(E.group_indices(plan, idx))
bases = jnp.asarray(plan.base_rows)
ref = E.lookup_unsharded(arenas, plan.base_rows, gidx, plan)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
lookup = E.make_sharded_lookup(mesh, plan)
with jax.set_mesh(mesh):
    out = lookup(arenas, bases, gidx)
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), "mismatch"
print("SHARDED_OK")
"""


def test_sharded_lookup_matches_oracle_8dev():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT, src],
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_padded_slots_add_nothing_and_get_no_gradient(setup):
    """Padded pooling slots (-1) point at arena row 0; the lookup must not
    add that row even when it is non-zero, nor train it."""
    model, params, plan, raw, idx, rng = setup
    gidx = E.group_indices(plan, idx)
    arenas = np.asarray(params["arenas"]).copy()
    arenas[:, 0] = 1.0                           # a row 0 that is not zero
    out = np.asarray(_oracle(plan)(jnp.asarray(arenas), None,
                                   jnp.asarray(gidx)))
    shard = np.repeat(np.arange(plan.n_shards), plan.k_max)
    base = plan.base_rows.reshape(-1)
    rows = arenas[shard[None, :, None], gidx + base[None, :, None]]
    ref = np.where((gidx >= 0)[..., None], rows, 0.0).sum(axis=2)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    g = jax.grad(lambda a: _oracle(plan)(a, None, jnp.asarray(gidx)).sum())(
        jnp.asarray(arenas))
    np.testing.assert_array_equal(np.asarray(g)[:, 0], 0.0)

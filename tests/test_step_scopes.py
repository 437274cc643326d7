"""The placed step names its layers: every scope of ``repro.models.dlrm``
and ``repro.embedding.sharded`` reaches the compiled step's ``op_name``
metadata, and the scopes change nothing else in the compiled program."""

import contextlib
import os
import re
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
from repro.models import dlrm
from repro.optim import adam, rowwise_adagrad

PROGRAM_SCOPES = (*dlrm.SCOPES, E.LOOKUP_SCOPE, E.EXCHANGE_SCOPE,
                  *E.BWD_SCOPES)
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                      r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


def tiny_step_hlo() -> str:
    """Compiled HLO text of a tiny placed step (8 tables on 4 shards, one
    device): ``lookup_unsharded``, row-wise Adagrad and Adam."""
    pool = make_dlrm_pool(seed=0)
    M, S = 8, 4
    raw = pool[:M].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 500)
    plan = build_plan(raw, np.arange(M) % S, S)
    model = dlrm.DLRM(dlrm.DLRMConfig(n_dense_features=4,
                                      embed_dim=plan.dim, bottom_mlp=(32,),
                                      top_mlp=(64, 32), n_tables=M),
                      plan, dtype=jnp.bfloat16)

    def lookup(a, b, i):
        return E.lookup_unsharded(a, plan.base_rows, i, plan)

    emb_opt, dense_opt = rowwise_adagrad(0.05), adam(1e-3)
    step = dlrm.make_train_step(model, lookup, emb_opt, dense_opt)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    es = jax.eval_shape(emb_opt.init, {"arenas": params["arenas"]})
    ds = jax.eval_shape(dense_opt.init,
                        {k: params[k] for k in dlrm.DENSE_PARAMS})
    B, P = 16, 5
    batch = {"dense": jax.ShapeDtypeStruct((B, 4), jnp.float32),
             "gidx": jax.ShapeDtypeStruct((B, S * plan.k_max, P),
                                          jnp.int32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.float32)}
    return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        params, es, ds, batch).compile().as_text()


def op_names(hlo: str) -> dict:
    """HLO instruction name -> ``op_name``."""
    return {m.group(1): m.group(2) for m in map(_OP_NAME.match,
                                                hlo.splitlines()) if m}


def scopes_of(op_name: str) -> set:
    out = set()
    for seg in re.split(r"[/;]", op_name):
        while (m := _WRAPPED.fullmatch(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


def without_metadata(hlo: str) -> str:
    """The compiled program: the module line and its computations, with
    each instruction's ``metadata`` and the source-location tables that
    only metadata points into left out."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    head, _, rest = hlo.partition("\n")
    return head + rest[rest.index("\n%") if "\n%" in rest
                       else rest.index("\nENTRY"):]


@contextlib.contextmanager
def program_scopes_off(names=PROGRAM_SCOPES):
    """``jax.named_scope`` as a null context for ``names``."""
    orig = jax.named_scope

    def scope(name):
        return contextlib.nullcontext() if name in names else orig(name)

    jax.named_scope = scope
    try:
        yield
    finally:
        jax.named_scope = orig


@pytest.fixture(scope="module")
def no_compile_cache():
    """The persistent compile cache off: its key leaves out op metadata,
    so a cached scoped step would come back for the bare one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def scoped(no_compile_cache):
    return tiny_step_hlo()


@pytest.mark.parametrize("scope",
                         dlrm.SCOPES + (E.LOOKUP_SCOPE,) + E.BWD_SCOPES)
def test_compiled_step_carries_every_scope(scoped, scope):
    assert any(scope in scopes_of(n) for n in op_names(scoped).values())


def test_lookup_backward_is_named_by_its_transpose(scoped):
    """The backward's sort, fetch and accumulate ops sit under the
    lookup's transpose, where a trace reduction files them as embedding
    backward (``transpose(`` and the lookup's scope in ``op_name``)."""
    names = list(op_names(scoped).values())
    for scope in E.BWD_SCOPES:
        under = [n for n in names if scope in scopes_of(n)]
        assert under, scope
        for n in under:
            assert "transpose(" in n and E.LOOKUP_SCOPE in scopes_of(n), n


def test_scopes_leave_the_compiled_step_unchanged(scoped):
    with program_scopes_off():
        bare = tiny_step_hlo()
    assert not any(set(PROGRAM_SCOPES) & scopes_of(n)
                   for n in op_names(bare).values())
    assert without_metadata(bare) == without_metadata(scoped)


_EXCHANGE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re, sys; sys.path.insert(0, sys.argv[1])
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
mesh = jax.make_mesh((1, S), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
lookup = E.make_sharded_lookup(mesh, plan)
arenas = jax.ShapeDtypeStruct((S, plan.rows_max, plan.dim), jnp.float32)
bases = jax.ShapeDtypeStruct(plan.base_rows.shape, jnp.int32)
gidx = jax.ShapeDtypeStruct((16, S * plan.k_max, 5), jnp.int32)

def loss(a, b, g):
    return jnp.sum(lookup(a, b, g) ** 2)

with jax.set_mesh(mesh):
    hlo = jax.jit(jax.grad(loss)).lower(arenas, bases, gidx).compile(
        ).as_text()
for line in hlo.splitlines():
    if re.search(r"\ball-to-all\(", line):
        name = re.search(r'op_name="([^"]*)"', line)
        print("A2A", name.group(1) if name else "")
"""


def test_sharded_exchange_carries_its_scope():
    """On four CPU devices, the all-to-alls of the lookup and of its
    gradient carry ``emb.exchange``."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _EXCHANGE_SCRIPT, src],
                       capture_output=True, text=True, timeout=600)
    names = [line[4:] for line in r.stdout.splitlines()
             if line.startswith("A2A ")]
    assert len(names) >= 2, r.stdout + r.stderr
    for name in names:
        assert E.EXCHANGE_SCOPE in scopes_of(name), name
    assert any("transpose(" in n for n in names), names
    assert any("transpose(" not in n for n in names), names

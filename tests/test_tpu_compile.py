"""Compile the main path's kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  This catches what interpret mode cannot -- block
shapes the tiling refuses, SMEM or VMEM overruns -- at the real widths of
the placed DLRM step (128 lanes, batch 65536, pooling 16, multi-million-row
arenas).  The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
running this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.embedding import sharded as E
from repro.kernels.embedding_bag.kernel import embedding_bag_fused


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows,n_bags,dtype", [
    (20_500_000, 65536 * 13, jnp.bfloat16),   # largest DLRM-50 shard
    (4_000_000, 65536, jnp.float32),
    (1_000_001, 65536, jnp.bfloat16),         # odd rows: one padding row
])
def test_embedding_bag_compiles_for_v5e(one_chip, rows, n_bags, dtype):
    arena = jax.ShapeDtypeStruct((rows, 128), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n_bags, 16), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, i: embedding_bag_fused(a, i, interpret=False)
    ).lower(arena, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # an arena whose rows fill whole 32-bit sublanes is viewed in place;
    # only an odd bf16 arena is copied, to pad its last pair
    arena_bytes = rows * 128 * jnp.dtype(dtype).itemsize
    copied = compiled.memory_analysis().temp_size_in_bytes >= arena_bytes
    assert copied == (dtype == jnp.bfloat16 and rows % 2 == 1)


@pytest.mark.parametrize("rows,batch,dim,dtype", [
    (20_512_829, 65536, 16, jnp.bfloat16),    # largest DLRM-50 shard
    (4_000_000, 4096, 128, jnp.float32),      # rows-major orientation
])
def test_lookup_backward_compiles_for_v5e(one_chip, rows, batch, dim, dtype):
    """The lookup's backward (13 slots of 16 per sample): the sorted row
    sums are the Pallas kernel, no scatter is left under the lookup's
    transpose, and the (R, D) gradient is written in place, never
    copied."""
    k, pool = 13, 16
    arena = jax.ShapeDtypeStruct((rows, dim), dtype, sharding=one_chip)
    bases = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch, k, pool), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((batch, k, dim), jnp.float32, sharding=one_chip)

    def backward(a, b, i, g):
        return jax.vjp(lambda a: E._local_lookup(a, b, i), a)[1](g)[0]

    compiled = jax.jit(backward).lower(arena, bases, idx, g).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    scatters = [line for line in hlo.splitlines()
                if re.search(r"\bscatter\(", line) and E.LOOKUP_SCOPE in line]
    assert not scatters, scatters[:2]
    # temporaries: the fetched f32 rows and four int32 slot arrays; a copy
    # of the (R, D) gradient would add rows * dim * itemsize more
    slots = batch * k * pool
    budget = slots * dim * 4 + 4 * slots * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= budget


def test_dlrm50_forward_gathers_row_groups_for_v5e(one_chip):
    """The dlrm50 cell's lookup forward, four shards at its widths
    (20,512,829 bf16 rows of 16 a shard, batch 65,536, 13 bags of 16):
    one gather a shard (in its column loop), of 128-lane row groups,
    with temporaries no larger than the per-row gather's; and the D-128 column lookup keeps
    gathering single rows, with no regroup."""
    from test_lane_grouped_lookup import per_row
    S, R, B, K, P, D = 4, 20_512_829, 65536, 13, 16, 16
    args = (jax.ShapeDtypeStruct((S, R, D), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((S, K), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B, S * K, P), jnp.int32, sharding=one_chip))

    def forward(lookup):
        return jax.jit(lambda a, b, i: jnp.concatenate(
            [lookup(a[s], b[s], i[:, s * K:(s + 1) * K]) for s in range(S)],
            axis=1)).lower(*args).compile()

    grouped, plain = forward(E._local_lookup), forward(per_row)
    hlo = grouped.as_text()
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    assert len(gathers) == S, gathers
    assert all("slice_sizes={1,128}" in line for line in gathers), gathers
    assert E.REGROUP_SCOPE in hlo
    assert (grouped.memory_analysis().temp_size_in_bytes
            <= plain.memory_analysis().temp_size_in_bytes)

    col_slot = tuple(np.repeat(np.arange(K), 16))
    cols = jax.jit(lambda a, b, i: E._local_lookup(a, b, i, col_slot)).lower(
        jax.ShapeDtypeStruct((1_000_000, 128), jnp.bfloat16,
                             sharding=one_chip),
        args[1].update(shape=(K,)),
        jax.ShapeDtypeStruct((8192, len(col_slot)), jnp.int32,
                             sharding=one_chip)).compile().as_text()
    assert E.REGROUP_SCOPE not in cols and "tpu_custom_call" not in cols
    assert all("slice_sizes={1,128}" in line for line in cols.splitlines()
               if " gather(" in line)


def _dcnv2_step(config, sharding):
    """The cell's DLRM-DCNv2 step (``bench/configs/dlrm_dcnv2.json``) on
    its normal path, and its argument shapes on ``sharding``."""
    import json
    import numpy as np
    from repro.core import features as F
    from repro.embedding.plan import build_plan
    from repro.models import dlrm
    from repro.optim import RowWiseAdagrad, adam
    with open(config) as f:
        c = json.load(f)
    rows = np.asarray(c["num_embeddings_per_feature"], np.float64)
    raw = np.zeros((rows.shape[0], F.NUM_FEATURES))
    raw[:, F.DIM], raw[:, F.HASH_SIZE] = c["embedding_dim"], rows
    plan = build_plan(raw, np.zeros(rows.shape[0], int), 1,
                      pad_dim_to=c["embedding_dim"],
                      widths=c["multi_hot_sizes"],
                      pad_rows_to=c["pad_rows_to"])
    cfg = dlrm.DLRMConfig(
        n_dense_features=c["num_dense_features"], embed_dim=plan.dim,
        bottom_mlp=tuple(c["dense_arch_layer_sizes"][:-1]),
        top_mlp=tuple(c["over_arch_layer_sizes"][:-1]),
        n_tables=rows.shape[0], interaction=c["interaction_type"],
        cross_layers=c["dcn_num_layers"], cross_rank=c["dcn_low_rank_dim"])
    model = dlrm.DLRM(cfg, plan, dtype=jnp.bfloat16)
    emb_opt = RowWiseAdagrad(c["emb_optimizer"]["lr"])
    dense_opt = adam(c["dense_optimizer"]["lr"])
    step = dlrm.make_train_step(
        model, lambda a, b, i: E.lookup_unsharded(a, plan.base_rows, i, plan),
        emb_opt, dense_opt)
    p = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = (p, jax.eval_shape(emb_opt.init, {"arenas": p["arenas"]}),
             jax.eval_shape(dense_opt.init,
                            {k: p[k] for k in cfg.dense_keys}))
    B = c["batch_size"]
    batch = {"dense": jax.ShapeDtypeStruct((B, c["num_dense_features"]),
                                           jnp.float32),
             "gidx": jax.ShapeDtypeStruct((B, plan.n_cols), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.float32)}
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), (*state, batch))
    return step, args, plan


def test_dcnv2_step_compiles_for_v5e(one_chip):
    """The dlrm_dcnv2 cell's whole step at its shapes (29.2 M arena rows
    of 128, batch 8,192, 214 ids a sample): it compiles, arguments and
    temporaries fit in 14 GB, and no temporary is as large as the arena,
    so no dense (R, 128) gradient or arena copy is kept; the lookup's
    forward is XLA's gather and the row update's sums and writes are
    Pallas kernels."""
    root = os.path.join(os.path.dirname(__file__), "..")
    step, args, plan = _dcnv2_step(
        os.path.join(root, "bench", "configs", "dlrm_dcnv2.json"), one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args).compile()
    m = compiled.memory_analysis()
    arena_bytes = plan.rows_max * plan.dim * 2
    assert m.argument_size_in_bytes + m.temp_size_in_bytes <= 14e9
    assert m.temp_size_in_bytes < arena_bytes
    hlo = compiled.as_text()
    assert "write_rows" in hlo and "sorted_row_sum" in hlo
    big = {s for s in re.findall(r"\b(f32|bf16|s32)\[(\d+),128\]", hlo)
           if int(s[1]) >= plan.rows_max}
    assert big <= {("bf16", str(plan.rows_max))}, big


# sha256 of the dlrm50 cell's step lowered for a described v5e, with each
# Pallas kernel's serialized body replaced by its text without source
# locations (``_lowered_without_locations``): the step whose lookup
# forward gathers 128-lane row groups (``sharded._row_groups``) of the
# live index columns alone, in one loop (``sharded.COLUMNS_SCOPE``)
DLRM50_LOWERED_SHA256 = ("a76ea166209e18d24c7b70e7cb5a2e4e"
                         "d584d23002b0f3ff2f6bfaeaef9af7ab")


def _lowered_without_locations(text: str) -> str:
    """Lowered StableHLO text with every Mosaic kernel body decoded and
    printed without debug info, so that moving a kernel's source lines
    leaves it unchanged."""
    import base64
    import json
    from jax._src.lib.mlir import ir

    def body(m):
        cfg = json.loads(m.group(1).replace("\\22", '"'))
        raw = base64.b64decode(cfg["custom_call_config"]["body"])
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            cfg["custom_call_config"]["body"] = ir.Module.parse(
                raw).operation.get_asm(enable_debug_info=False)
        return "backend_config = " + json.dumps(cfg, sort_keys=True)

    return re.sub(r'backend_config = "(\{.*?\})"', body, text)


def dlrm50_lowered(sharding) -> str:
    """The dlrm50 cell's step, as ``bench/program.py`` builds it, lowered
    for ``sharding``'s device."""
    import json
    import sys
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import program
    from bench.pool import make_pool
    from repro.models.dlrm import DENSE_PARAMS, make_train_step
    with open(os.path.join(root, "bench", "configs", "dlrm50.json")) as f:
        cfg = json.load(f)
    pool, _ = make_pool(cfg["pool"]["n_tables"], cfg["pool"]["seed"])
    raw = pool[:cfg["n_tables"]]
    prog = program.build(raw, program.place(raw, cfg), cfg, None)
    step = jax.jit(make_train_step(prog.model, prog.lookup, prog.emb_opt,
                                   prog.dense_opt), donate_argnums=(0, 1, 2))
    p = jax.eval_shape(prog.model.init_params, jax.random.PRNGKey(0))
    es = jax.eval_shape(prog.emb_opt.init, {"arenas": p["arenas"]})
    ds = jax.eval_shape(prog.dense_opt.init, {k: p[k] for k in DENSE_PARAMS})
    B, S, K = cfg["batch"], prog.plan.n_shards, prog.plan.k_max
    batch = {"dense": jax.ShapeDtypeStruct((B, 13), jnp.float32),
             "gidx": jax.ShapeDtypeStruct((B, S * K, cfg["max_pooling"]),
                                          jnp.int32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.float32)}
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), (p, es, ds, batch))
    return step.lower(*args).as_text()


def test_dlrm50_lowered_step_is_unchanged(one_chip):
    """The dlrm50 cell's step, lowered for a v5e, is the one pinned
    above, apart from its kernels' source locations: a change to the
    step updates the digest on purpose."""
    import hashlib
    text = _lowered_without_locations(dlrm50_lowered(one_chip))
    assert hashlib.sha256(text.encode()).hexdigest() == DLRM50_LOWERED_SHA256

"""Compile rehearsal of the placed DLRM step on a production mesh.

Lowers and compiles one DLRM train step -- table-parallel embedding
(``shard_map`` + all-to-all) on a DreamShard-style plan, data-parallel
dense nets -- for a mesh of host devices standing in for a TPU v5e pod,
and records the compiled footprint per device and its roofline terms
(``repro.launch.roofline``).  No chip is needed: ``main`` forces the host
platform to 512 devices before JAX starts its backend.

Meshes: ``single`` = one pod of 256 chips as (data=16, model=16);
``multi`` = two pods, 512 chips as (pod=2, data=16, model=16), the pod
axis folded into data parallelism.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single
"""

import argparse
import json
import os
import time

import jax

from repro.launch import roofline as R

HOST_DEVICES = 512
MESHES = {"single": ((16, 16), ("data", "model")),
          "multi": ((2, 16, 16), ("pod", "data", "model"))}


def make_mesh(kind: str):
    shape, axes = MESHES[kind]
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _lower_dlrm(mesh, batch=65536, n_tables=160, pool_slots=16):
    """Paper's own architecture: DLRM train step with table-parallel
    embedding (shard_map + all-to-all), DreamShard-style placement plan.
    The tables go to the mesh's ``model`` axis, the batch to all others.

    Arenas are stored at the native dim (16): padded to 128 lanes they
    would take 8x the HBM.  The step looks rows up with XLA's gather of
    128-lane row groups (``embedding/sharded.py``).  Hash sizes
    are clipped to 4e6 rows so the 160-table pool fits a v5e-16 shard
    budget (the paper's 11 GB GPUs hold ~20-80 tables per device)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import baselines as B
    from repro.core import features as F
    from repro.data.synthetic import make_dlrm_pool
    from repro.embedding import sharded as E
    from repro.embedding.plan import build_plan
    from repro.models.dlrm import DLRM, DLRMConfig, make_train_step
    from repro.optim import adam, rowwise_adagrad
    from repro.optim.optimizers import OptState
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = "model"
    data = tuple(a for a in mesh.axis_names if a != m)
    tp = mesh.shape[m]
    pool = make_dlrm_pool(seed=0)[:n_tables].copy()
    pool[:, F.HASH_SIZE] = np.clip(pool[:, F.HASH_SIZE], 1e4, 4e6)
    pool[:, F.TABLE_SIZE_GB] = F.table_size_gb(pool[:, F.DIM],
                                               pool[:, F.HASH_SIZE])
    assign = B.expert_place(pool, tp, 1e9, "size")
    plan = build_plan(pool, assign, tp, pad_dim_to=16)
    cfg = DLRMConfig(n_dense_features=13, embed_dim=plan.dim,
                     bottom_mlp=(512, 256), top_mlp=(1024, 512, 256),
                     n_tables=n_tables)
    model = DLRM(cfg, plan, dtype=jnp.bfloat16)
    lookup = E.make_sharded_lookup(mesh, plan, data_axes=data, model_axis=m)
    emb_opt = rowwise_adagrad(0.05)
    dense_opt = adam(1e-3)
    train_step = make_train_step(model, lookup, emb_opt, dense_opt)

    aparams = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    a_emb = jax.eval_shape(emb_opt.init, {"arenas": aparams["arenas"]})
    a_dense = jax.eval_shape(
        dense_opt.init, {k: aparams[k] for k in ("bottom", "top")})
    batch_specs = {
        "dense": jax.ShapeDtypeStruct((batch, 13), jnp.float32),
        "gidx": jax.ShapeDtypeStruct(
            (batch, plan.n_shards * plan.k_max, pool_slots), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    pspecs = {"arenas": P(m, None, None),
              "bottom": [{"w": P(None, None), "b": P(None)}
                         for _ in aparams["bottom"]],
              "top": [{"w": P(None, None), "b": P(None)}
                      for _ in aparams["top"]]}
    e_specs = OptState(P(), {"arenas": P(m, None)})   # rowwise acc (S, R)
    d_specs = jax.tree.map(lambda x: P() if getattr(x, "ndim", 0) == 0
                           else P(None, None) if x.ndim == 2 else P(None),
                           a_dense)
    b = data if len(data) > 1 else data[0]
    bspec = {"dense": P(b, None), "gidx": P(b, None, None),
             "labels": P(b)}

    def ns(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda s: isinstance(s, P))

    in_sh = (ns(pspecs), ns(e_specs), ns(d_specs), ns(bspec))
    out_sh = (ns(pspecs), ns(e_specs), ns(d_specs),
              NamedSharding(mesh, P()))
    fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0, 1, 2))
    return fn.lower(aparams, a_emb, a_dense, batch_specs)


def run_dlrm(mesh, **sizes) -> dict:
    """Lower and compile the step on ``mesh`` (``sizes``: ``_lower_dlrm``'s
    batch, n_tables, pool_slots); its times, bytes per device and
    roofline terms."""
    rec = {"mesh_shape": dict(mesh.shape), "n_devices": mesh.size}
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = _lower_dlrm(mesh, **sizes)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    ca = compiled.cost_analysis()
    wire = R.collective_wire_bytes(compiled.as_text(), mesh.shape["model"])
    terms = R.RooflineTerms(
        hlo_flops=float(ca.get("flops", 0.0)),
        hlo_bytes=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=sum(wire.values()), wire_by_kind=wire,
        model_flops=0.0, n_devices=mesh.size)
    rec.update({"lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "arg_bytes_per_dev": int(ma.argument_size_in_bytes),
                "out_bytes_per_dev": int(ma.output_size_in_bytes),
                "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
                "alias_bytes_per_dev": int(ma.alias_size_in_bytes),
                "peak_bytes_per_dev": int(peak),
                "fits_16gb_hbm": bool(peak < 16e9),
                "roofline": terms.as_dict()})
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", choices=sorted(MESHES),
                    help="one mesh (default: both)")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={HOST_DEVICES}").strip()

    results = []
    for kind in [args.mesh] if args.mesh else list(MESHES):
        rec = {**run_dlrm(make_mesh(kind)), "mesh": kind}
        print(f"{kind}: compile={rec['compile_s']}s "
              f"peak={rec['peak_bytes_per_dev'] / 1e9:.2f}GB/dev "
              f"dominant={rec['roofline']['dominant']}", flush=True)
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        jax.clear_caches()


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles on the production mesh, and extract the
roofline terms for EXPERIMENTS.md.

For each combination:
  1. full-depth `lower().compile()` with the layer scan -- the lowering
     proof; `memory_analysis()` from this compile shows the footprint.
  2. unrolled 1-layer and 2-layer metric compiles -- cost_analysis FLOPs/
     bytes and parsed collective wire bytes, extrapolated to full depth
     (cost_analysis counts a scan body once; see launch/roofline.py).

Results append incrementally to a JSON file so partial runs are resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --shape train_4k --mesh single                           # one combo
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro import configs as C  # noqa: E402
from repro.configs.shapes import INPUT_SHAPES  # noqa: E402
from repro.launch import roofline as R  # noqa: E402
from repro.launch.mesh import make_production_mesh, production_rules  # noqa: E402
from repro.launch import steps as ST  # noqa: E402

TP = 16
# decode cache capacity for sliding-window archs on the 500k shape
LONG_DECODE_WINDOW = {"h2o-danube-1.8b": 4096, "hymba-1.5b": 1024,
                      "rwkv6-1.6b": None}


def _lower(cfg, shape, mesh, rules, layer_loop, remat=True,
           n_microbatches=1):
    with jax.set_mesh(mesh):
        return _lower_inner(cfg, shape, mesh, rules, layer_loop, remat,
                            n_microbatches)


def _lower_inner(cfg, shape, mesh, rules, layer_loop, remat=True,
                 n_microbatches=1):
    if shape.kind == "train":
        lowered, _ = ST.lower_train(cfg, shape, mesh, rules,
                                    layer_loop=layer_loop, remat=remat,
                                    n_microbatches=n_microbatches)
    elif shape.kind == "prefill":
        lowered, _ = ST.lower_prefill(cfg, shape, mesh, rules,
                                      layer_loop=layer_loop, remat=remat)
    else:
        window = None
        if shape.name == "long_500k":
            window = LONG_DECODE_WINDOW.get(cfg.name.split("-smoke")[0])
        lowered, _ = ST.lower_decode(cfg, shape, mesh, rules,
                                     window_capacity=window,
                                     layer_loop=layer_loop)
    return lowered


def _lower_dlrm(mesh, rules, batch=65536, n_tables=160, pool_slots=16):
    """Paper's own architecture: DLRM train step with table-parallel
    embedding (shard_map + all-to-all), DreamShard-style placement plan.

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

    tp = mesh.shape["model"]
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
    lookup = E.make_sharded_lookup(mesh, plan,
                                   data_axes=rules.batch_axes or ("data",),
                                   model_axis=rules.model_axis)
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
    m = rules.model_axis
    pspecs = {"arenas": P(m, None, None),
              "bottom": [{"w": P(None, None), "b": P(None)}
                         for _ in aparams["bottom"]],
              "top": [{"w": P(None, None), "b": P(None)}
                      for _ in aparams["top"]]}
    e_specs = OptState(P(), {"arenas": P(m, None)})   # rowwise acc (S, R)
    d_specs = jax.tree.map(lambda x: P() if getattr(x, "ndim", 0) == 0
                           else P(None, None) if x.ndim == 2 else P(None),
                           a_dense)
    bspec = {"dense": rules.spec("batch", None),
             "gidx": rules.spec("batch", None, None),
             "labels": rules.spec("batch")}
    def ns(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda s: isinstance(s, P))

    in_sh = (ns(pspecs), ns(e_specs), ns(d_specs), ns(bspec))
    out_sh = (ns(pspecs), ns(e_specs), ns(d_specs),
              NamedSharding(mesh, P()))
    fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0, 1, 2))
    return fn.lower(aparams, a_emb, a_dense, batch_specs)


def run_dlrm(mesh_kind: str) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rules = production_rules(multi_pod=multi)
    rec = {"arch": "dlrm", "shape": "train_65k", "mesh": mesh_kind,
           "n_devices": mesh.size, "status": "ok"}
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = _lower_dlrm(mesh, rules)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    ca = compiled.cost_analysis()
    from repro.launch import roofline as R
    wire = R.collective_wire_bytes(compiled.as_text(), 16)
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


def run_combo(arch: str, shape_name: str, mesh_kind: str,
              skip_metrics: bool = False, strategy: str = "tp",
              n_microbatches: int = 1) -> dict:
    if arch == "dlrm":
        return run_dlrm(mesh_kind)
    shape = INPUT_SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rules = production_rules(multi_pod=multi, strategy=strategy)
    n_dev = mesh.size
    cfg = C.get_full(arch).resolve(1 if strategy == "fsdp" else TP)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "n_devices": n_dev, "status": "ok", "strategy": strategy}
    t0 = time.perf_counter()

    rec["n_microbatches"] = n_microbatches
    # 1) full-depth lowering proof (scan over layers)
    lowered = _lower(cfg, shape, mesh, rules, "scan",
                     n_microbatches=n_microbatches)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    ma = compiled.memory_analysis()
    rec.update({
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "arg_bytes_per_dev": int(ma.argument_size_in_bytes),
        "out_bytes_per_dev": int(ma.output_size_in_bytes),
        "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
        "alias_bytes_per_dev": int(ma.alias_size_in_bytes),
    })
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec["peak_bytes_per_dev"] = int(peak)
    rec["fits_16gb_hbm"] = bool(peak < 16e9)
    del compiled, lowered

    if skip_metrics:
        return rec

    # 2) metric compiles: unrolled depth 1 and 2, extrapolate to L
    L = cfg.n_layers
    metrics = {}
    for k in (1, 2):
        cfg_k = dataclasses.replace(cfg, n_layers=k)
        lw = _lower(cfg_k, shape, mesh, rules, "unrolled",
                    n_microbatches=n_microbatches)
        cp = lw.compile()
        ca = cp.cost_analysis()
        metrics[k] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "wire": R.collective_wire_bytes(cp.as_text(), TP),
        }
        del cp, lw
    flops = R.extrapolate(metrics[1]["flops"], metrics[2]["flops"], L)
    bytes_ = R.extrapolate(metrics[1]["bytes"], metrics[2]["bytes"], L)
    wire_by_kind = {
        k: R.extrapolate(metrics[1]["wire"][k], metrics[2]["wire"][k], L)
        for k in metrics[1]["wire"]}
    terms = R.RooflineTerms(
        hlo_flops=flops, hlo_bytes=bytes_,
        wire_bytes=sum(wire_by_kind.values()), wire_by_kind=wire_by_kind,
        model_flops=R.model_flops(cfg, shape), n_devices=n_dev)
    rec["roofline"] = terms.as_dict()
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    return rec


def iter_combos(archs, shapes, meshes):
    for arch in archs:
        if arch == "dlrm":          # paper's own arch: one training shape
            for mesh in meshes:
                yield arch, "train_65k", mesh
            continue
        for shape in shapes:
            if not C.supports_shape(arch, shape):
                continue
            for mesh in meshes:
                yield arch, shape, mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--skip-metrics", action="store_true")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip combos already in the output file")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(C.ARCH_NAMES)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    try:
        results = json.load(open(args.out))
    except (FileNotFoundError, json.JSONDecodeError):
        results = []
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") == "ok"} if args.skip_done else set()

    for arch, shape, mesh in iter_combos(archs, shapes, meshes):
        if (arch, shape, mesh) in done:
            continue
        print(f"== {arch} x {shape} x {mesh} ==", flush=True)
        try:
            rec = run_combo(arch, shape, mesh,
                            skip_metrics=args.skip_metrics,
                            strategy=args.strategy,
                            n_microbatches=args.microbatches)
            rl = rec.get("roofline", {})
            print(f"   ok compile={rec['compile_s']}s "
                  f"peak={rec['peak_bytes_per_dev']/1e9:.2f}GB/dev "
                  f"dominant={rl.get('dominant', '-')}", flush=True)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": mesh,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"   ERROR {type(e).__name__}: {e}", flush=True)
        results = [r for r in results
                   if (r["arch"], r["shape"], r["mesh"]) != (arch, shape, mesh)]
        results.append(rec)
        json.dump(results, open(args.out, "w"), indent=1)
        jax.clear_caches()

    n_ok = sum(r.get("status") == "ok" for r in results)
    print(f"done: {n_ok}/{len(results)} combos ok")


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) cell this lowers and
compiles the cell's step (train / prefill / decode) against the production
mesh — 16x16 single-pod and 2x16x16 multi-pod — using ShapeDtypeStruct
stand-ins (no allocation), then records:

  * per-device memory analysis (proves the cell fits HBM),
  * cost analysis (FLOPs / bytes for the roofline),
  * the collective schedule (wire bytes per collective kind),
  * the three roofline terms + bottleneck (launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k \
      --mesh single [--out benchmarks/artifacts/dryrun] [--opts ...]
  python -m repro.launch.dryrun --all --mesh both     # every cell, one proc

``main()`` gives the CPU backend 512 placeholder devices; a caller of
``run_cell`` sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before the backend starts.
"""
import argparse
import os
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def _mesh(kind: str):
    from .mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def _per_device_arg_bytes(args) -> int:
    total = 0
    for leaf in jax.tree.leaves(args):
        shard = leaf.sharding.shard_shape(leaf.shape)
        n = 1
        for d in shard:
            n *= d
        total += n * leaf.dtype.itemsize
    return total


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             opts: dict | None = None) -> dict:
    from ..configs.base import SHAPE_BY_NAME, cell_is_runnable, get_config
    from ..distributed.steps import make_step_bundle
    from ..optim.adamw import AdamWConfig
    from .mesh import PRODUCTION_DEVICE_KIND
    from .roofline import collective_bytes, roofline_terms

    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "kind": shape.kind, "opts": opts or {}}
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = _mesh(mesh_kind)
    kw = dict(opts or {})
    # translate string/flag opts into builder kwargs (perf-iteration knobs)
    if kw.pop("act_seq_shard", None):
        # Megatron-style sequence parallelism for the residual stream
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..distributed.sharding import axis_size, dp_axes
        dp = dp_axes(mesh)
        dpx = (dp if len(dp) > 1 else dp[0]) if (
            dp and shape.global_batch % axis_size(mesh, dp) == 0) else None
        kw.setdefault("extra_hints", {})["activations"] = NamedSharding(
            mesh, P(dpx, "model", None))
    if kw.pop("moe_dshard", None):
        # decode: keep expert weights sharded; shard expert-buffer d dim on
        # "data" so the FFN contraction partial-sums + all-reduces
        # activations instead of all-gathering expert weights
        from jax.sharding import NamedSharding, PartitionSpec as P
        kw.setdefault("extra_hints", {})["moe_expert_in"] = NamedSharding(
            mesh, P("model", None, None, "data"))
    if kw.get("cache_l_model") is not None:
        kw["cache_l_model"] = bool(kw["cache_l_model"])
    if isinstance(kw.get("param_dtype"), str):
        kw["param_dtype"] = jnp.dtype(kw["param_dtype"])
    # big-model dry-runs default to bf16 Adam moments (DESIGN.md §5)
    if shape.kind == "train":
        kw.setdefault("opt_cfg", AdamWConfig(
            moment_dtype=kw.pop("moment_dtype", "bfloat16")))
    else:
        kw.pop("cast_params", None)
    if shape.kind != "decode":
        kw.pop("cache_l_model", None)
    t0 = time.time()
    bundle = make_step_bundle(cfg, mesh, shape, **kw)
    lowered = bundle.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    from .roofline import normalize_cost_analysis
    cost = normalize_cost_analysis(compiled.cost_analysis())
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_rec = {"error": str(e)}
    mem_rec["arg_bytes_analytic_per_device"] = _per_device_arg_bytes(
        bundle.args)

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from .roofline import hlo_stats
    stats = hlo_stats(hlo)   # loop-corrected (cost_analysis counts loop
    #                          bodies once; see roofline.hlo_stats)
    terms = roofline_terms(stats, coll, mesh.size, cfg, shape,
                           device_kind=PRODUCTION_DEVICE_KIND)
    terms["xla_flops_unscaled"] = cost.get("flops")
    terms["xla_bytes_unscaled"] = cost.get("bytes accessed")

    rec.update({
        "status": "ok",
        "step": bundle.name,
        "dispatch": bundle.meta.get("dispatch"),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "cost": {k: v for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "collectives": coll,
        "roofline": terms,
        "hlo_bytes": len(hlo),
    })
    return rec


def main() -> int:
    # placeholder devices for the production meshes; read when the
    # backend starts, which nothing before this point has done
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))
    from ..configs.base import SHAPES, cell_is_runnable, get_config, \
        list_archs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--remat", type=int, default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--dispatch", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="extra builder opts, e.g. --set cast_params=1 "
                         "--set param_dtype=bfloat16 --set act_seq_shard=1")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s.name) for a in list_archs() for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    opts = {}
    if args.remat is not None:
        opts["remat"] = bool(args.remat)
    if args.microbatch is not None:
        opts["microbatch"] = args.microbatch
    if args.dispatch is not None:
        opts["dispatch"] = args.dispatch
    for kv in args.set:
        k, _, v = kv.partition("=")
        opts[k] = int(v) if v.isdigit() else v

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            name = f"{args.tag}--{arch}--{shape}--{mesh_kind}.json"
            path = os.path.join(args.out, name)
            if os.path.exists(path) and not args.force:
                print(f"[skip-cached] {name}")
                continue
            t0 = time.time()
            try:
                # train-only opts must not leak into serve cells
                cell_kind = next(s.kind for s in SHAPES
                                 if s.name == shape)
                kw = dict(opts)
                if cell_kind != "train":
                    kw.pop("remat", None)
                    kw.pop("microbatch", None)
                rec = run_cell(arch, shape, mesh_kind, kw)
            except Exception:
                rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "status": "error",
                       "error": traceback.format_exc(limit=20)}
                failures += 1
            rec["wall_s"] = round(time.time() - t0, 2)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, default=str)
            status = rec.get("status")
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f" bottleneck={r['bottleneck']}"
                         f" comp={r['compute_s']:.3e}s"
                         f" mem={r['memory_s']:.3e}s"
                         f" coll={r['collective_s']:.3e}s"
                         f" compile={rec['compile_s']:.0f}s")
            elif status == "skipped":
                extra = f" ({rec['reason']})"
            print(f"[{status}] {arch} x {shape} x {mesh_kind}{extra}",
                  flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell on the production meshes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch grok-1-314b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

For each cell this prints ``compiled.memory_analysis()`` (proves it fits
HBM) and ``compiled.cost_analysis()`` (FLOPs/bytes for §Roofline), parses
collective bytes from the compiled HLO, and appends the roofline record to
the output JSON consumed by EXPERIMENTS.md.

The XLA_FLAGS assignment above MUST run before any jax import — jax locks
the device count at first init.  Do not set it globally (tests and benches
see 1 device).
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, cell_supported
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import HW, analytic_hbm_bytes, roofline_from_counts
from repro.launch.specs import make_cell


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    verbose: bool = True,
    moe_backend: str | None = None,
    remat: str | None = None,
    microbatches: int | None = None,
    sp_shardmap: bool = False,
):
    import dataclasses

    cfg = get_config(arch)
    if moe_backend and cfg.is_moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, backend=moe_backend)
        )
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if sp_shardmap:
        cfg = dataclasses.replace(cfg, sp_shardmap=True)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    mesh_name = "x".join(map(str, mesh.devices.shape))
    t0 = time.time()
    fn, args = make_cell(cfg, shape, mesh, microbatches=microbatches)
    donate = getattr(fn, "donate_argnums", ())
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax wraps the dict in a list
        cost = cost[0] if cost else {}
    if verbose:
        print(f"--- {arch} / {shape_name} / mesh {mesh_name} ---")
        print("memory_analysis:", mem)
        print("cost_analysis keys:", {k: v for k, v in sorted(cost.items())
                                      if isinstance(v, (int, float)) and v})

    # Trip-aware per-device FLOPs / HBM bytes / collective bytes from the
    # optimized HLO (cost_analysis counts while bodies once — see
    # hlo_analysis docstring); cost_analysis itself is printed above.
    hlo = compiled.as_text()
    analysis = analyze_hlo(
        hlo, chips,
        f32_collective_scale=0.5 if cfg.dtype == "bfloat16" else 1.0,
    )
    coll_by_kind = analysis["collectives"]
    coll_counts = analysis["collective_counts"]
    per_dev_coll = float(sum(coll_by_kind.values()))

    flops_per_dev = float(analysis["flops"])
    # Memory numerator: analytic TPU-granularity traffic (the parsed count
    # inherits CPU fusion granularity — kept as a diagnostic upper bound).
    parsed_bytes_per_dev = float(analysis["hbm_bytes"])
    bytes_global = analytic_hbm_bytes(cfg, shape)
    bytes_per_dev = bytes_global / chips
    per_dev_hbm = float(
        getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )

    # Tokens processed by one step of this cell.
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch  # one token per sequence
    mult = 3.0 if shape.kind == "train" else 1.0  # fwd + bwd ~ 3x fwd
    model_flops = 2.0 * cfg.active_param_count() * tokens * mult

    terms = roofline_from_counts(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=flops_per_dev * chips,
        hlo_bytes=bytes_per_dev * chips,
        collective_bytes=per_dev_coll * chips,
        model_flops=model_flops,
        per_device_hbm_peak=per_dev_hbm,
    )
    rec = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "mesh": mesh_name,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "collectives": {k: v for k, v in sorted(coll_by_kind.items())},
        "collective_counts": coll_counts,
        "hlo_bytes_parsed_per_dev": parsed_bytes_per_dev,
        "cost_analysis_flops_per_dev": float(cost.get("flops", 0.0)),
        "cost_analysis_bytes_per_dev": float(cost.get("bytes accessed", 0.0)),
        "fits_hbm": bool(per_dev_hbm <= HW().hbm_bytes),
        **{k: (float(v) if isinstance(v, (int, float)) else v)
           for k, v in terms.to_dict().items()},
    }
    if verbose:
        print("collectives/dev:", {k: f"{v/1e9:.2f}GB" for k, v in
                                   sorted(coll_by_kind.items())}, coll_counts)
        print(
            f"roofline: compute={terms.compute_s*1e3:.2f}ms "
            f"memory={terms.memory_s*1e3:.2f}ms "
            f"collective={terms.collective_s*1e3:.2f}ms "
            f"bottleneck={terms.bottleneck} useful={terms.useful_ratio:.2f} "
            f"HBM/dev={per_dev_hbm/1e9:.2f}GB fits={rec['fits_hbm']}"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all cells, both meshes")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--moe-backend", choices=("einsum", "mixnet"), default=None,
                    help="override the MoE dispatch backend (perf hillclimb)")
    ap.add_argument("--remat", choices=("none", "full", "dots"), default=None)
    ap.add_argument("--microbatches", type=int, default=None,
                    help="gradient-accumulation microbatches for train cells")
    ap.add_argument("--sp", action="store_true",
                    help="explicit Megatron-SP shard_map (beyond-paper perf)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                cells.append((arch, shape, False))
                if not args.single_pod_only:
                    cells.append((arch, shape, True))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required without --all")
        cells.append((args.arch, args.shape, args.multi_pod))

    results = []
    if args.out and os.path.exists(args.out):
        results = json.load(open(args.out))
        done = {(r["arch"], r["shape"], r["multi_pod"]) for r in results}
        cells = [c for c in cells if c not in done]

    failures = 0
    for arch, shape, mp in cells:
        try:
            rec = run_cell(arch, shape, mp, moe_backend=args.moe_backend,
                           remat=args.remat, microbatches=args.microbatches,
                           sp_shardmap=args.sp)
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "FAILED", "error": str(e)[:500]}
            failures += 1
        results.append(rec)
        if args.out:
            json.dump(results, open(args.out, "w"), indent=1)
    print(f"\n{len(results)} cells recorded, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

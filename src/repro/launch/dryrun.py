import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and emit roofline terms.

MUST be run as a fresh process (``python -m repro.launch.dryrun``): the
XLA_FLAGS line above executes before any jax import so the CPU platform
exposes 512 placeholder devices for ``jax.make_mesh``.

Per cell:
    1. build the step program (repro.launch.steps.build_cell),
    2. jit with in/out shardings, ``.lower()`` on ShapeDtypeStructs
       (no real allocation anywhere),
    3. ``.compile()`` — a sharding mismatch, OOM-at-compile or unsupported
       collective here is a bug in the framework,
    4. record ``memory_analysis()`` / ``cost_analysis()`` / parsed
       collective bytes into experiments/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
    python -m repro.launch.dryrun                      # all cells, both meshes
    python -m repro.launch.dryrun --arch fm --shape train_batch
    python -m repro.launch.dryrun --mesh single        # 16x16 only
"""

import argparse
import json
import time
import traceback


def _cost_variant(arch, shape_name: str):
    """Clone the ArchSpec with every lax.scan unrolled (and the equiformer
    edge scan re-chunked to <= 4 trips): HLO cost analysis counts a while
    body ONCE, so the production scan-based program under-reports
    flops/bytes/collectives by the trip count.  The cost variant computes
    the same function with full counting; the production variant remains
    the compile-proof + memory-analysis artifact.

    Only families whose programs contain scans need it: LM (layer scan,
    kv-chunk scan, CE-chunk scan) and equiformer's ogb edge scan.  GCN/
    PNA/MeshGraphNet layers are Python loops (already unrolled); FM and
    JEDI-net have no scans.
    """
    import dataclasses
    model = arch.model
    if arch.family == "lm":
        return dataclasses.replace(
            arch, model=dataclasses.replace(model, unroll_scans=True))
    if arch.family == "gnn" and model.kind == "equiformer_v2" \
            and shape_name == "ogb_products":
        return dataclasses.replace(
            arch, model=dataclasses.replace(
                model, unroll_scans=True, edge_chunk=1 << 24))
    return None


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: str, no_cost_variant: bool = False) -> dict:
    import jax
    from repro.configs.registry import get_arch
    from repro.launch import roofline
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell
    from repro.parallel.sharding import axis_rules

    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_arch(arch_id)
    shape = arch.shapes[shape_name]
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"

    rec = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "chips": int(mesh.devices.size), "status": "error",
    }
    t0 = time.time()
    try:
        prog = build_cell(arch, shape, mesh)
        args, in_sh, out_sh = prog.make_abstract()
        with mesh:
            with axis_rules(mesh):
                jitted = jax.jit(prog.step_fn, in_shardings=in_sh,
                                 out_shardings=out_sh)
                lowered = jitted.lower(*args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        rec.update(roofline.from_compiled(compiled, mesh))
        rec.update({
            "status": "ok",
            "kind": prog.kind,
            "notes": prog.notes,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
        })
        # --- cost variant: scans unrolled for complete op counting.
        # Single-pod only: the §Roofline table is single-pod per the spec;
        # the multi-pod pass is the pod-axis sharding proof.
        arch_c = (None if (multi_pod or no_cost_variant)
                  else _cost_variant(arch, shape_name))
        if arch_c is not None:
            try:
                t1 = time.time()
                prog_c = build_cell(arch_c, shape, mesh)
                args_c, in_sh_c, out_sh_c = prog_c.make_abstract()
                with mesh:
                    with axis_rules(mesh):
                        compiled_c = jax.jit(
                            prog_c.step_fn, in_shardings=in_sh_c,
                            out_shardings=out_sh_c).lower(*args_c).compile()
                cost = roofline.from_compiled(compiled_c, mesh)
                rec["roofline_scan"] = rec["roofline"]
                rec["roofline"] = cost["roofline"]
                rec["collectives"] = cost["collectives"]
                rec["cost_variant"] = {
                    "compile_s": round(time.time() - t1, 2),
                    "note": "scans unrolled for counting; memory stats "
                            "remain from the production scan variant",
                }
            except Exception as e:  # noqa: BLE001
                rec["cost_variant"] = {"error": f"{type(e).__name__}: {e}"}
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]

    if out_dir:
        os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
        path = os.path.join(out_dir, mesh_name,
                            f"{arch_id}__{shape_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--include-jedi", action="store_true",
                    help="also run the paper's own jedinet cells")
    ap.add_argument("--no-cost-variant", action="store_true",
                    help="skip the unrolled cost variant (MoE train cells "
                         "compile too slowly unrolled; their roofline rows "
                         "carry the analytic x n_layers correction instead)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.configs.registry import ASSIGNED_ARCHS, get_arch
    from repro.configs.registry import archs as family_archs

    archs = ([args.arch] if args.arch
             else ASSIGNED_ARCHS + (family_archs("jedinet")
                                    if args.include_jedi else []))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch_id in archs:
        spec = get_arch(arch_id)
        shapes = ([args.shape] if args.shape
                  else list(spec.runnable_shapes()))
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                tag = f"{arch_id} x {shape_name} @ {mesh_name}"
                t0 = time.time()
                rec = run_cell(arch_id, shape_name, mp, args.out,
                               no_cost_variant=args.no_cost_variant)
                dt = time.time() - t0
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[ok]   {tag}: bound={r['bound']} "
                          f"step={r['step_s']*1e3:.2f}ms "
                          f"(c={r['compute_s']*1e3:.2f} "
                          f"m={r['memory_s']*1e3:.2f} "
                          f"x={r['collective_s']*1e3:.2f}) {dt:.0f}s")
                else:
                    n_fail += 1
                    print(f"[FAIL] {tag}: {rec['error']}")
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()
# The two lines above MUST run before any other import pulls in jax: the
# device count locks on first backend initialization. Everything below is
# the multi-pod dry-run driver (deliverable e).
"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import pathlib
import sys
import time
import traceback


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: pathlib.Path,
             verbose: bool = True) -> dict:
    import jax
    from ..configs.registry import get_arch
    from ..roofline.analysis import analyze
    from .mesh import make_production_mesh
    from .steps import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    lowered = cell.lower(mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    spec = get_arch(arch)
    model_flops = None
    if spec.family == "lm":
        cfg = spec.config
        c = spec.shape(shape)
        if c.kind == "train":
            tokens = c.dims["global_batch"] * c.dims["seq_len"]
            model_flops = 6.0 * cfg.n_active_params() * tokens
        elif c.kind == "prefill":
            tokens = c.dims["global_batch"] * c.dims["seq_len"]
            model_flops = 2.0 * cfg.n_active_params() * tokens
        else:
            tokens = c.dims["global_batch"]
            model_flops = 2.0 * cfg.n_active_params() * tokens

    # the production meshes are pods of v5e chips: the roofline terms
    # assume that chip whatever device compiled the cell
    roof = analyze(arch, shape, mesh_name, chips, compiled,
                   device_kind="TPU v5 lite", model_flops=model_flops)
    mem_txt = None
    try:
        mem_txt = str(compiled.memory_analysis())
    except Exception:
        pass
    rec = roof.to_dict()
    rec.update({"lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "memory_analysis": mem_txt,
                "status": "ok"})
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
    path.write_text(json.dumps(rec, indent=2, default=str))
    if verbose:
        print(f"[ok] {arch} x {shape} x {mesh_name}: "
              f"compile={t_compile:.1f}s "
              f"flops/dev={rec['hlo_flops_per_device']:.3e} "
              f"coll/dev={rec['coll_bytes_per_device']:.3e} "
              f"bottleneck={rec['bottleneck']}")
        print(f"     memory_analysis: {mem_txt}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--include-matcher", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from ..configs.registry import all_cells
    out_dir = pathlib.Path(args.out)
    cells = (all_cells(include_matcher=args.include_matcher) if args.all
             else [(args.arch, args.shape)])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            name = f"{arch}__{shape}__{'pod2x16x16' if mp else 'pod16x16'}"
            if args.skip_existing and (out_dir / f"{name}.json").exists():
                print(f"[skip] {name}")
                continue
            try:
                run_cell(arch, shape, mp, out_dir)
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                failures.append((name, repr(e)))
                (out_dir / f"{name}.json").write_text(json.dumps(
                    {"arch": arch, "shape": shape, "multi_pod": mp,
                     "status": "fail", "error": repr(e)}, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for n, e in failures:
            print(" ", n, e[:200])
        return 1
    print("\nall cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch × shape) cell on the
# production mesh and record memory / cost / collective analyses.
#
# The two lines above MUST stay first — jax locks the device count at first
# init, and the dry-run needs 512 placeholder host devices to build the
# (2, 16, 16) mesh. Smoke tests and benchmarks never import this module.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all          # 40 cells x 2 meshes
#   PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod-only
#
# Artifacts: one JSON per (arch, shape, mesh) under --out (default
# artifacts/dryrun), with cost_analysis, memory_analysis, parsed HLO costs
# (trip-count-aware flops / bytes / collective payloads) and timings.

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze
from repro.train.step import build_cell


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             out_dir: pathlib.Path, save_hlo: bool = False) -> dict:
    spec = get_arch(arch_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch_id}__{shape_name}__{mesh_name}"
    t0 = time.time()
    step_fn, state_abs, inputs_abs = build_cell(spec, shape_name, mesh)
    t_build = time.time() - t0

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_fn).lower(state_abs, inputs_abs)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ca = compiled.cost_analysis() or {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_size_in_bytes": ma.argument_size_in_bytes,
            "output_size_in_bytes": ma.output_size_in_bytes,
            "temp_size_in_bytes": ma.temp_size_in_bytes,
            "alias_size_in_bytes": ma.alias_size_in_bytes,
            "generated_code_size_in_bytes": ma.generated_code_size_in_bytes,
        }
    except Exception as e:  # pragma: no cover
        mem = {"error": repr(e)}

    hlo_text = compiled.as_text()
    t0 = time.time()
    parsed = analyze(hlo_text)
    t_parse = time.time() - t0

    n_dev = mesh.devices.size
    result = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "n_devices": n_dev,
        "timings_s": {"build": round(t_build, 2), "lower": round(t_lower, 2),
                      "compile": round(t_compile, 2),
                      "hlo_parse": round(t_parse, 2)},
        "cost_analysis": {k: ca.get(k) for k in
                          ("flops", "bytes accessed", "utilization")
                          if k in ca},
        "memory_analysis": mem,
        "hlo_parsed": parsed.to_json(),
        "hlo_size_chars": len(hlo_text),
        "ok": True,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=1))
    if save_hlo:
        import gzip
        with gzip.open(out_dir / f"{tag}.hlo.txt.gz", "wt") as f:
            f.write(hlo_text)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    if args.all:
        cells = []
        for aid in ARCH_IDS:
            spec = get_arch(aid)
            for sh in spec.shapes:
                meshes = [False, True]
                if args.single_pod_only:
                    meshes = [False]
                if args.multi_pod_only:
                    meshes = [True]
                for mp in meshes:
                    cells.append((aid, sh, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape, args.multi_pod)]

    failures = []
    for aid, sh, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        tag = f"{aid}__{sh}__{mesh_name}"
        if args.skip_existing and (out_dir / f"{tag}.json").exists():
            prev = json.loads((out_dir / f"{tag}.json").read_text())
            if prev.get("ok"):
                print(f"[skip] {tag}")
                continue
        t0 = time.time()
        try:
            res = run_cell(aid, sh, multi_pod=mp, out_dir=out_dir,
                           save_hlo=args.save_hlo)
            hp = res["hlo_parsed"]
            print(f"[ok]   {tag}  compile={res['timings_s']['compile']}s "
                  f"flops/dev={hp['flops']:.3e} "
                  f"coll/dev={hp['collective_bytes']:.3e}B "
                  f"temp={res['memory_analysis'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
        except Exception as e:
            failures.append(tag)
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / f"{tag}.json").write_text(json.dumps(
                {"arch": aid, "shape": sh, "mesh": mesh_name, "ok": False,
                 "error": traceback.format_exc()}, indent=1))
            print(f"[FAIL] {tag}  {time.time()-t0:.1f}s  {e}")
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print(f"\nall {len(cells)} cells OK")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a module entry point BEFORE any other jax-touching import —
the XLA_FLAGS line above executes first, forcing 512 placeholder host
devices so jax.make_mesh can build the production meshes.

Per cell it records: compile success, memory_analysis (bytes/device),
cost_analysis (FLOPs / bytes), and the collective-op byte census parsed from
the compiled HLO — everything the roofline module (repro.roofline) consumes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single                            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --out results.json
"""
import argparse
import json
import re
import time
import traceback
from collections import Counter

import jax


def _collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective in the (SPMD-partitioned) HLO."""
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
                   "u64": 8, "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    out = {op: {"count": 0, "bytes": 0} for op in ops}
    # lines look like:  %ag = f32[16,1024]{1,0} all-gather(...)
    pat = re.compile(
        r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
        + "|".join(ops) + r")\(")
    for mt in pat.finditer(hlo_text):
        dt, shape_s, op = mt.groups()
        if dt not in dtype_bytes:
            continue
        numel = 1
        if shape_s:
            for d in shape_s.split(","):
                numel *= int(d)
        out[op]["count"] += 1
        out[op]["bytes"] += numel * dtype_bytes[dt]
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, out: dict,
             variant: str | None = None) -> None:
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cell_specs

    mesh_name = "multi" if multi_pod else "single"
    key = f"{arch}|{shape}|{mesh_name}"
    if variant:
        key += f"|{variant}"
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "variant": variant}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            spec = cell_specs(arch, shape, mesh, variant=variant)
            if "skip" in spec:
                rec["status"] = "skipped"
                rec["reason"] = spec["skip"]
                out[key] = rec
                print(f"SKIP {key}: {spec['skip'][:60]}")
                return
            fn = spec["fn"]
            jitted = jax.jit(fn, donate_argnums=spec.get("donate", ()))
            t_l = time.time()
            lowered = jitted.lower(*spec["args"])
            rec["lower_s"] = round(time.time() - t_l, 1)
            t_c = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t_c, 1)

            ma = compiled.memory_analysis()
            print(ma)
            if ma is not None:
                rec["memory"] = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                }
            ca = compiled.cost_analysis()
            print({k: ca.get(k) for k in ("flops", "bytes accessed")})
            if ca:
                rec["cost"] = {
                    "flops": float(ca.get("flops", -1)),
                    "bytes_accessed": float(ca.get("bytes accessed", -1)),
                }
            txt = compiled.as_text()
            rec["collectives"] = _collective_bytes(txt)  # static census
            from repro.roofline.census import census
            rec["census"] = census(txt)                  # trip-count-aware
            rec["hlo_ops"] = dict(Counter(
                m.group(1) for m in re.finditer(
                    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
                    r"collective-permute|fusion|custom-call|scatter|gather)\(",
                    txt)))
            rec["status"] = "ok"
            rec["total_s"] = round(time.time() - t0, 1)
            print(f"OK   {key} (lower {rec['lower_s']}s, "
                  f"compile {rec['compile_s']}s)")
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"FAIL {key}: {rec['error'][:200]}")
    out[key] = rec


def main() -> None:
    from repro.configs.base import SHAPES
    from repro.configs.registry import list_archs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true",
                    help="merge into existing --out file")
    ap.add_argument("--variant", default=None,
                    choices=[None, "tiered_experts", "fsdp", "local_grads"],
                    help="perf-pass variant (EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out: dict = {}
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            out = json.load(f)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                run_cell(arch, shape, mp, out, variant=args.variant)
                with open(args.out, "w") as f:   # checkpoint after each cell
                    json.dump(out, f, indent=1)

    n_ok = sum(1 for r in out.values() if r["status"] == "ok")
    n_skip = sum(1 for r in out.values() if r["status"] == "skipped")
    n_err = sum(1 for r in out.values() if r["status"] == "error")
    print(f"\ndry-run complete: {n_ok} ok / {n_skip} skipped / {n_err} failed "
          f"-> {args.out}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

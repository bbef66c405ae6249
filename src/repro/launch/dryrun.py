import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=512"
# ^ MUST run before any other import triggers jax initialization: the dry-run
#   builds the production meshes (256-chip pod / 512-chip 2-pod) from host
#   placeholder devices.  Everything below this line may import jax.

# Multi-pod dry-run: prove every (architecture x input-shape x mesh) lowers,
# compiles, fits, and report its cost/memory/collective profile.
#
# Usage:
#     python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
#     python -m repro.launch.dryrun --all                  # every combo, 1-pod
#     python -m repro.launch.dryrun --all --mesh multi     # 2-pod (512 chips)
#
# Outputs one JSON per combo under experiments/dryrun/.
# (No module docstring / __future__ import: the XLA_FLAGS lines above must be
#  the first statements in the file.)

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import (ARCHITECTURES, INPUT_SHAPES, get_config,
                           long_context_ok)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def cost_analysis_dict(obj) -> dict:
    """{Lowered,Compiled}.cost_analysis(), or {} where XLA gives none."""
    return obj.cost_analysis() or {}


def applicable(arch: str, shape: str) -> bool:
    cfg = get_config(arch)
    if shape == "long_500k" and not long_context_ok(cfg):
        return False        # pure full-attention archs skip 500k decode (DESIGN.md)
    return True


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    if not out and ma is not None:
        out["repr"] = str(ma)
    return out


def run_one(arch: str, shape: str, mesh_kind: str = "single", *,
            save: bool = True, verbose: bool = True,
            variant: str = "baseline") -> dict:
    from repro.launch import steps as steps_mod
    t0 = time.perf_counter()
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "mesh_shape": dict(zip(mesh.axis_names,
                                  [int(mesh.shape[a]) for a in mesh.axis_names])),
           "variant": variant, "ok": False}
    from repro import runtime_flags
    runtime_flags.set_variant(variant, mesh)
    try:
        lowered, kind = steps_mod.lower_step(cfg, shape, mesh)
        rec["kind"] = kind
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)
        ca = cost_analysis_dict(compiled)
        rec["cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
        # scan-aware global cost: re-lower with every lax.scan unrolled (cheap
        # — no compile) because XLA cost analysis counts a while body once.
        from repro import runtime_flags
        try:
            runtime_flags.set_unroll(True)
            unrolled, _ = steps_mod.lower_step(cfg, shape, mesh)
            uca = cost_analysis_dict(unrolled)
            rec["global_cost"] = {
                "flops": float(uca.get("flops", 0.0)),
                "bytes_accessed": float(uca.get("bytes accessed", 0.0)),
            }
        finally:
            runtime_flags.set_unroll(False)
        rec["memory_analysis"] = memory_analysis_dict(compiled)
        hlo = compiled.as_text()
        rec["collectives"] = hlo_analysis.collective_bytes(hlo)
        rec["op_histogram"] = hlo_analysis.op_histogram(hlo)
        rec["ok"] = True
        if verbose:
            print(f"[OK] {arch} x {shape} x {mesh_kind} "
                  f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s, "
                  f"flops={rec['cost_analysis']['flops']:.3e}, "
                  f"coll={rec['collectives']['total_bytes']:.3e}B)")
    except Exception as e:   # a failure here is a sharding/system bug
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[FAIL] {arch} x {shape} x {mesh_kind}: {rec['error']}")
    finally:
        runtime_flags.set_variant("baseline")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        tag = f"{arch}_{shape}_{mesh_kind}" + \
            (f"_{variant}" if variant != "baseline" else "")
        with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHITECTURES))
    ap.add_argument("--shape", default=None, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    from repro import runtime_flags as _rf
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(_rf.VARIANTS))
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    combos = []
    archs = sorted(ARCHITECTURES) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            if applicable(a, s):
                combos.append((a, s))
            else:
                print(f"[SKIP] {a} x {s} (full-attention arch; see DESIGN.md)")

    failures = 0
    for mesh_kind in meshes:
        for a, s in combos:
            rec = run_one(a, s, mesh_kind, variant=args.variant)
            failures += 0 if rec["ok"] else 1
    print(f"\n{len(combos) * len(meshes)} combos, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())

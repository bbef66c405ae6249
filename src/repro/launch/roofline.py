"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh),
derived from the dry-run artifacts in experiments/dryrun/.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from the scan-unrolled lowering's cost analysis
(global totals — XLA counts a while body once, so the dry-run re-lowers with
scans unrolled; see dryrun.py).  Collective bytes come from the compiled
SPMD executable's HLO with while-body trip-count scaling
(hlo_analysis.collective_bytes); shapes there are per-device shards, and
all-reduce is weighted 2x (reduce-scatter + all-gather on the wire).

Usage:
    python -m repro.launch.roofline                  # report over all JSONs
    python -m repro.launch.roofline --mesh single --markdown
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.configs import INPUT_SHAPES, get_config
from repro.core.devices import chip_peaks

# the dry-run's production meshes are v5e pods
_CHIP = chip_peaks("TPU v5 lite")

# wire-traffic weight per collective type (ring algorithms, large N)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")


def model_flops(arch: str, shape: str) -> float:
    """Analytic MODEL_FLOPS: 6*N*D (train) / 2*N_active*D + attention (serve)."""
    cfg = get_config(arch)
    sh = INPUT_SHAPES[shape]
    b, s, kind = sh["global_batch"], sh["seq_len"], sh["kind"]
    if kind == "train":
        return 3.0 * cfg.flops_per_token(s) * b * s      # fwd+bwd = 3x fwd
    if kind == "prefill":
        return float(cfg.flops_per_token(s)) * b * s
    return float(cfg.flops_per_token(s)) * b             # decode: 1 tok/sample


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float
    note: str
    variant: str = "baseline"

    def as_dict(self):
        return self.__dict__.copy()


def analyze_record(rec: dict) -> Optional[RooflineRow]:
    if not rec.get("ok"):
        return None
    chips = 1
    for v in rec["mesh_shape"].values():
        chips *= v
    gflops = rec.get("global_cost", {}).get("flops", 0.0)
    gbytes = rec.get("global_cost", {}).get("bytes_accessed", 0.0)
    compute_s = gflops / (chips * _CHIP.flops)
    memory_s = gbytes / (chips * _CHIP.hbm_bw)
    coll = rec.get("collectives", {}).get("bytes", {})
    wire = sum(v * _WIRE_FACTOR.get(k, 1.0) for k, v in coll.items())
    collective_s = wire / _CHIP.link_bw    # bytes already per-chip shards
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    ratio = mf / gflops if gflops else 0.0
    note = _note(rec, dominant, ratio)
    return RooflineRow(rec["arch"], rec["shape"], rec["mesh"], chips,
                       compute_s, memory_s, collective_s, dominant, mf,
                       gflops, ratio, note,
                       variant=rec.get("variant", "baseline"))


def _note(rec: dict, dominant: str, ratio: float) -> str:
    coll = rec.get("collectives", {}).get("bytes", {})
    biggest_coll = max(coll, key=coll.get) if coll else "none"
    if dominant == "collective":
        return (f"dominated by {biggest_coll}; reshard to cut it "
                f"(e.g. keep activations model-sharded through the stack)")
    if dominant == "memory":
        if rec["shape"].startswith(("decode", "long")):
            return ("KV/state streaming bound; fuse cache read+attend "
                    "(decode kernel) or quantize cache to int8")
        return "activation traffic bound; increase fusion / remat less"
    if ratio < 0.5:
        return ("compute-bound but HLO does >2x model FLOPs; cut remat "
                "recompute or f32 upcasts")
    return "compute-bound near useful-FLOPs roofline; scale batch or chips"


def load_rows(mesh: Optional[str] = None, variant: str = "baseline"
              ) -> List[RooflineRow]:
    rows = []
    for f in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        rec = json.load(open(f))
        if mesh and rec.get("mesh") != mesh:
            continue
        if rec.get("variant", "baseline") != variant:
            continue
        row = analyze_record(rec)
        if row:
            rows.append(row)
    return rows


def markdown_table(rows: List[RooflineRow]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | MODEL/HLO | note |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r.arch} | {r.shape} | {r.mesh} | {r.compute_s:.2e} | "
            f"{r.memory_s:.2e} | {r.collective_s:.2e} | **{r.dominant}** | "
            f"{r.useful_ratio:.2f} | {r.note} |")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    rows = load_rows(args.mesh, args.variant)
    if args.markdown:
        print(markdown_table(rows))
    else:
        for r in rows:
            print(f"{r.arch:24s} {r.shape:12s} {r.mesh:6s} "
                  f"C={r.compute_s:.2e} M={r.memory_s:.2e} "
                  f"X={r.collective_s:.2e} -> {r.dominant:10s} "
                  f"useful={r.useful_ratio:.2f}")
    if args.json_out:
        json.dump([r.as_dict() for r in rows], open(args.json_out, "w"),
                  indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

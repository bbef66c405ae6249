"""Find everything by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``, whose
``reference`` names the plain reference module beside it, which defines
:data:`REFERENCE_API`) and a traffic mix (``traffic/<traffic>.json``).  A
metric ``<quantity>`` or ``<quantity>.<cell tag>`` is read by
``metrics/<quantity>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from pathlib import Path
from typing import List

# What every configuration's reference module defines:
#   shapes(cfg) -> {leaf: shape}, in the program's layout
#   init_params(key, cfg, dtype) -> params, traceable, made on the device
#   last_logits(params, tokens, cfg, *, weights="stored"|"int8") -> (B, vocab)
#   program_config(cfg) -> the program's config object at these sizes
#   param_bytes(cfg, itemsize) -> bytes of the weights
#   rehearsal(cfg) -> the configuration at a size a CPU test holds, with
#       limits of its own
#   model_flops_per_row(cfg, seq) -> FLOPs one member needs for one row
REFERENCE_API = ("shapes", "init_params", "last_logits", "program_config",
                 "param_bytes", "rehearsal", "model_flops_per_row")
LIMITS = {"max_abs_err", "rms_rel_err"}
# keys that hold a width, which a cut never changes: a *_dim or *_rank, a
# head, hidden, intermediate, latent, state, projection or window size, an
# expansion factor, the experts each token is routed to.  A vocabulary may be
# sliced to the chip's share.
WIDTH = re.compile(r"(_dim|_rank|_size|expand|_ratio|window|experts_per_tok"
                   r"|experts_per_token)$")
SLICEABLE = {"vocab_size"}

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in manifest['workloads']]})")


def config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                cfg = json.load(f)
            cfg["name"] = name
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def check_config(entry: dict, cfg: dict) -> None:
    """Refuse a configuration file that does not say how it was cut:
    ``entry`` is its entry in ``BENCHMARK.json``, ``cfg`` the file.  Every
    key in ``reduced`` is a key of the file and no width; a cut file
    states its ``deployment`` and, under ``published``, the published value
    of every reduced key, which differs from the one it holds.  Raises
    ``ValueError`` naming every fault."""
    faults = []
    reduced = cfg.get("reduced")
    if entry.get("reduced") != reduced:
        faults.append(f"reduced is {entry.get('reduced')!r} in BENCHMARK.json"
                      f" and {reduced!r} in the file")
    reduced = reduced or []
    published = cfg.get("published") or {}
    for k in reduced:
        if k not in cfg:
            faults.append(f"reduced key {k!r} is not in the file")
        if WIDTH.search(k) and k not in SLICEABLE:
            faults.append(f"reduced key {k!r} is a width")
        if k not in published:
            faults.append(f"reduced key {k!r} has no published value")
        elif k in cfg and published[k] == cfg[k]:
            faults.append(f"reduced key {k!r} holds its published value "
                          f"{cfg[k]!r}")
    if reduced and not cfg.get("deployment"):
        faults.append("a cut file states no deployment")
    if not cfg.get("members", 0) >= 1:
        faults.append(f"members is {cfg.get('members')!r}")
    if set(cfg.get("limits") or {}) != LIMITS:
        faults.append(f"limits are {sorted(cfg.get('limits') or {})}, "
                      f"not {sorted(LIMITS)}")
    if faults:
        raise ValueError(f"configuration {entry.get('name')!r}: "
                         + "; ".join(faults))


def traffic_path(traffic: str) -> str:
    return str(HERE / "traffic" / f"{traffic}.json")


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict):
    """The configuration's plain reference module."""
    name = cfg["reference"]
    return _load_module(HERE / "configs" / f"{name}.py",
                        f"chipbench_ref_{name}")


def metric_reader(name: str):
    """The reader module of per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r}")
    return _load_module(path, "chipbench_metric_" + name.replace(".", "_"))


def metrics_of(manifest: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it under ``workloads``, or that list no workloads."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def cache_dir(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")

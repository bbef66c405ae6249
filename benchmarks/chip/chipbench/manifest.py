"""Find everything by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``, whose
``reference`` names the plain reference module beside it) and a traffic mix
(``traffic/<traffic>.json``).  A metric ``<quantity>`` or
``<quantity>.<cell tag>`` is read by ``metrics/<quantity>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from pathlib import Path
from typing import List

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

"""Device abstraction for the allocation problem.

The paper's "device" is one GPU or CPU socket.  Our generalization (DESIGN.md
§2): a device is an **allocation cell** — one chip, or a sub-mesh slice with
model-parallel sharding inside.  ``jax_devices`` carries the backing runtime
devices: real TPU chips for :func:`tpu_cells`, the host's CPU device for
:func:`host_cpus` (every CPU cell shares it while keeping a distinct
*logical* memory budget, which is exactly what the allocation algorithms
reason about).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence, Tuple

import jax

GiB = 1024 ** 3


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float          # dense bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: int        # HBM capacity
    link_bw: float        # bytes/s per inter-chip link


# The one table of chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is the v5e: Google Cloud documentation, "TPU v5e" — 197
# TFLOP/s bf16, 16 GiB HBM2 at 819 GB/s, 1,600 Gbit/s of ICI over four links.
CHIPS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * GiB,
                             link_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind missing from :data:`CHIPS` is an
    error, never a default."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r} "
            f"(known: {sorted(CHIPS)})") from None


# Reference V100 / host constants for paper-shaped simulated clusters
V100_PEAK_FLOPS = 125e12 / 8         # fp32 tensor-core derate for inference mix
V100_HBM_BW = 900e9
V100_HBM_BYTES = 32 * GiB
HOST_PEAK_FLOPS = 1.5e12
HOST_BW = 80e9


@dataclass(frozen=True)
class DeviceSpec:
    name: str
    kind: str                        # "GPU" | "CPU" | "TPU"
    memory_bytes: int
    peak_flops: float
    mem_bw: float
    jax_devices: Tuple = ()          # backing jax.Device cell (may be empty = simulated)

    @property
    def is_accelerator(self) -> bool:
        return self.kind in ("GPU", "TPU")

    def key(self) -> str:
        return f"{self.kind}:{self.name}:{self.memory_bytes}"


def simulated_gpus(n: int, memory_bytes: int = V100_HBM_BYTES) -> list:
    return [DeviceSpec(f"gpu{i}", "GPU", memory_bytes, V100_PEAK_FLOPS, V100_HBM_BW)
            for i in range(n)]


def host_cpus(n: int = 1, memory_bytes: int = 16 * GiB) -> list:
    """CPU cells, each backed by the host's CPU device — also when an
    accelerator is JAX's default backend."""
    backing = tuple(jax.devices("cpu")[:1])
    return [DeviceSpec(f"cpu{i}", "CPU", memory_bytes, HOST_PEAK_FLOPS, HOST_BW,
                       jax_devices=backing) for i in range(n)]


def _memory_limit(device, default: int) -> int:
    """Bytes the runtime lets one device allocate, where it reports them."""
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_limit", default))


def tpu_cells(mesh_devices: Sequence, cell_size: int) -> list:
    """Partition a flat device list into model-parallel cells of ``cell_size``
    chips each — the beyond-paper 'cells' extension (DESIGN.md §9.2).  Peaks
    come from :data:`CHIPS` by the chips' ``device_kind``; a cell's memory is
    the sum of its chips' runtime ``bytes_limit`` (the table's HBM size where
    the runtime reports none)."""
    cells = []
    flat = list(mesh_devices)
    for i in range(0, len(flat) - cell_size + 1, cell_size):
        group = tuple(flat[i:i + cell_size])
        peaks = chip_peaks(group[0].device_kind)
        mem = sum(_memory_limit(d, peaks.hbm_bytes) for d in group)
        cells.append(DeviceSpec(
            f"cell{i // cell_size}", "TPU", mem,
            peaks.flops * cell_size, peaks.hbm_bw * cell_size,
            jax_devices=group))
    return cells

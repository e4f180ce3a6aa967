"""The chip: its published peaks, and the refusal to run anywhere else."""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass(frozen=True)
class Peak:
    kind: str
    flops_per_s: float       # dense bf16 matmul peak
    bytes_per_s: float       # HBM bandwidth
    hbm_bytes: float
    source: str


def peak(device_kind: str, path: Path = PEAKS_FILE) -> Peak:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in the peak table "
            f"{path.name} (known: {sorted(table)})")
    e = table[device_kind]
    return Peak(device_kind, float(e["bf16_flops_per_s"]),
                float(e["hbm_bytes_per_s"]), float(e["hbm_bytes"]),
                e["source"])


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; raises ``NoAccelerator`` otherwise."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX platform is {devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out

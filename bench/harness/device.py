"""The accelerator the cell runs on: presence, identity, memory and peaks."""
from __future__ import annotations

import json
from pathlib import Path

from .manifest import ROOT


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoAccelerator` otherwise
    (never falls back to the CPU)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < n:
        raise NoAccelerator(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    import jax

    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of ``kind``; a device missing from the table is
    an error, not a default."""
    with open(Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]

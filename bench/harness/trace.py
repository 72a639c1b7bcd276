"""From a profiler trace to device busy time, kernel time and launch bytes.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small neutral :class:`Trace`: per device, the operations of its ``XLA Ops``
and ``Async XLA Ops`` lines; on the host, the benchmark's own ``bench.*`` spans.  Everything else
works on that structure, so the tests can feed it a recorded trace.

Busy time is the union of a device's operation intervals inside the
window (the ``bench.window`` span), so nested events (a ``while`` around
its body) count once.  Every share here is a ratio of such unions.
"""
from __future__ import annotations

import dataclasses
import json
from collections import defaultdict
from typing import Dict, List, Tuple

from . import hlo

Interval = Tuple[float, float]

#: the device lines that hold operations: the compute stream and the
#: asynchronous one (copies, collectives)
DEVICE_LINES = ("XLA Ops", "Async XLA Ops")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {
            "devices": {d: [[e.name, e.start, e.end] for e in evs]
                        for d, evs in self.devices.items()},
            "host": [[e.name, e.start, e.end] for e in self.host],
        }

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls(
            devices={d: [Event(*e) for e in evs] for d, evs in doc["devices"].items()},
            host=[Event(*e) for e in doc["host"]],
        )

    @classmethod
    def read(cls, path) -> "Trace":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def window(self) -> Interval:
        for e in self.host:
            if e.name == "bench.window":
                return e.start, e.end
        raise ValueError("the trace has no bench.window span")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: the operations on device planes
    ``/device:TPU:<n>`` and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    evs.extend(Event(e.name, e.start_ns, e.end_ns) for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name.startswith("bench."))
    if not devices:
        raise ValueError(f"{path}: the trace holds no TPU device plane")
    return Trace(devices=devices, host=host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Op:
    ins: hlo.Instruction
    start: float
    end: float


def ops(trace: Trace, device: str) -> List[Op]:
    """The device's leaf operations (control flow around them left out)."""
    out = []
    for e in trace.devices[device]:
        ins = hlo.parse(e.name) or hlo.Instruction(e.name, e.name.split(".")[0],
                                                   [], [], "")
        if ins.opcode not in hlo.CONTAINERS:
            out.append(Op(ins, e.start, e.end))
    return out


def _busy(trace: Trace, device: str, lo: float, hi: float, keep=None):
    return union(clip([(o.start, o.end) for o in ops(trace, device)
                       if keep is None or keep(o.ins)], lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window()
    per = [length(_busy(trace, d, lo, hi)) for d in trace.devices]
    return sum(per) / len(per) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float:
    """Percent of the window in which no operation ran on the device."""
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def share_outside(trace: Trace, inside) -> float | None:
    """Percent of device busy time outside the operations ``inside`` picks;
    ``None`` where none of them ran."""
    lo, hi = trace.window()
    busy = picked = 0.0
    for d in trace.devices:
        busy += length(_busy(trace, d, lo, hi))
        picked += length(_busy(trace, d, lo, hi, inside))
    if picked == 0.0 or busy == 0.0:
        return None
    return 100.0 * (1.0 - picked / busy)


def kernel_roofline(trace: Trace, hbm_bytes_per_s: float, inside=hlo.is_stencil_kernel):
    """The launches' bytes at the HBM peak over their device time, percent,
    with the bytes, seconds, launches and field cells written beside it.  ``None`` where no
    launch ran in the window."""
    lo, hi = trace.window()
    moved = seconds = cells = 0.0
    launches = 0
    for d in trace.devices:
        for o in ops(trace, d):
            if inside(o.ins) and o.start >= lo and o.end <= hi:
                moved += hlo.launch_bytes(o.ins)
                cells += sum(hlo.nbytes([(dt, dims)]) // hlo.DTYPE_BYTES[dt]
                             for dt, dims in o.ins.result if len(dims) >= 3)
                seconds += (o.end - o.start) / 1e9
                launches += 1
    if launches == 0 or seconds == 0.0:
        return None
    return {"share": 100.0 * moved / hbm_bytes_per_s / seconds,
            "bytes": moved, "seconds": seconds, "launches": launches,
            "cells": cells}


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The leaf operations that took most device time, per chip, in seconds;
    named ``<opcode> <instruction>``."""
    lo, hi = trace.window()
    total: Dict[str, float] = defaultdict(float)
    for d in trace.devices:
        for o in ops(trace, d):
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                total[f"{o.ins.opcode} {o.ins.name}"] += (e - s) / 1e9
    k = len(trace.devices)
    return [[name, t / k] for name, t in sorted(total.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The longest gaps in device 0's busy time, each named by the innermost
    ``bench.*`` host span over its midpoint."""
    lo, hi = trace.window()
    dev = sorted(trace.devices)[0]
    gaps = minus([(lo, hi)], _busy(trace, dev, lo, hi))
    spans = [e for e in trace.host if e.name != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        over = [h for h in spans if h.start <= mid <= h.end]
        name = min(over, key=lambda h: h.end - h.start).name if over else "bench.window"
        out.append([name, (e - s) / 1e9])
    return out

"""A trace's device operations with each distinct instruction text parsed
once, for the readers of a cell traced on several chips.

A 40 s window on four chips holds millions of device events but a few
hundred distinct instruction texts, and :func:`bench.harness.trace.ops`
parses the text of every event on each call: each pass over such a trace
takes minutes.  :func:`device_ops` parses each text once and keeps the
result for the trace being read, so every reader after the first reads it
for free; the numbers are those of :mod:`bench.harness.trace` and
:mod:`bench.harness.scopes` (the same leaf operations, window and unions).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from . import hlo, scopes
from . import trace as tr

Op = Tuple[float, float, hlo.Instruction]

#: ``(trace, ops)`` of the trace read last
_last: list = []


def device_ops(trace: tr.Trace) -> Dict[str, List[Op]]:
    """Per device, ``(start, end, instruction)`` of its leaf operations
    (control flow around them left out), as :func:`bench.harness.trace.ops`
    gives them."""
    if _last and _last[0] is trace:
        return _last[1]
    seen: Dict[str, hlo.Instruction] = {}
    out = {}
    for d, events in trace.devices.items():
        kept = []
        for e in events:
            ins = seen.get(e.name)
            if ins is None:
                ins = seen[e.name] = (hlo.parse(e.name) or hlo.Instruction(
                    e.name, e.name.split(".")[0], [], [], ""))
            if ins.opcode not in hlo.CONTAINERS:
                kept.append((e.start, e.end, ins))
        out[d] = kept
    _last[:] = [trace, out]
    return out


def by_scope(trace: tr.Trace, scope_map: dict) -> Dict[str, Dict[tuple, list]]:
    """Per device, the window-clipped union of the operations' intervals
    grouped as :func:`bench.harness.scopes.split` groups them: ``(scope,
    None)`` for a scoped operation, ``(None, "<opcode> <name>")`` for one
    the map does not know."""
    lo, hi = trace.window()
    names: Dict[int, tuple] = {}
    out = {}
    for d, ops in device_ops(trace).items():
        groups: Dict[tuple, list] = defaultdict(list)
        for start, end, ins in ops:
            group = names.get(id(ins))
            if group is None:
                scope = scope_map.get(scopes.key(ins))
                group = names[id(ins)] = ((scope, None) if scope else
                                          (None, f"{ins.opcode} {ins.name}"))
            groups[group].append((start, end))
        out[d] = {g: tr.union(tr.clip(iv, lo, hi)) for g, iv in groups.items()}
    return out


def busy(groups: Dict[tuple, list]) -> list:
    """One device's busy intervals from its :func:`by_scope` groups."""
    return tr.union(iv for ivs in groups.values() for iv in ivs)


def share(trace: tr.Trace, scope_map: Optional[dict], group, note: dict,
          key: str) -> Optional[float]:
    """Percent of device busy time, over every device, in operations whose
    scope is one of ``group``; ``None`` where none ran.  ``note[key]`` gets
    the split by scope in :func:`bench.harness.scopes.note_split`'s form."""
    if scope_map is None:
        return None
    per: Dict[str, float] = defaultdict(float)
    off: Dict[str, float] = defaultdict(float)
    busy_ns = picked = 0.0
    devices = by_scope(trace, scope_map)
    for groups in devices.values():
        for (scope, name), iv in groups.items():
            if scope:
                per[scope] += tr.length(iv)
            else:
                off[name] += tr.length(iv)
        busy_ns += tr.length(busy(groups))
        picked += tr.length(tr.union(iv for (scope, _), ivs in groups.items()
                                     if scope in group for iv in ivs))
    k = len(devices) * 1e9
    mean_busy = busy_ns / k
    parts = [f"{s} {t / k:.6f} s" for s, t in sorted(per.items(), key=lambda x: -x[1])]
    big = [(n, t / k) for n, t in sorted(off.items(), key=lambda x: -x[1])
           if t / k > scopes.NOTE_SHARE * mean_busy]
    rest = sum(off.values()) / k - sum(t for _, t in big)
    parts += [f"unscoped {n} {t:.6f} s" for n, t in big]
    parts.append(f"unscoped other {rest:.6f} s")
    total = (sum(per.values()) + sum(off.values())) / k
    note[key] = (", ".join(parts) + f"; sum {total:.6f} s of busy {mean_busy:.6f} s "
                 f"({100.0 * (total / mean_busy - 1.0):+.4f} %)")
    if picked == 0.0 or busy_ns == 0.0:
        return None
    return 100.0 * picked / busy_ns

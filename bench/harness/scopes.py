"""Split a device trace by the program's own names, and read its host spans.

The program names its work ``wfa.<layer>.<what>`` (``repro.engine.stats``):

* device scopes (``jax.named_scope``) on the operations it compiles, which
  ``device_scopes()`` maps from each compiled instruction, keyed by its
  name and result shapes as the trace prints them, to the innermost
  ``wfa.*`` scope;
* host spans (``span``), kept with ``time.perf_counter_ns()`` times in an
  in-memory ring that ``spans()`` reads.  The window's own times come from
  ``time.perf_counter()``, so the span readers stay on that one clock and
  never compare it with the trace's.

Instruction names are unique only within one compiled program.  The trace
keeps no program id, so an operation is matched by name and shapes alone.
``device_scopes()`` leaves out every key on which two recorded programs
disagree, but programs the program does not record (the benchmark's own
copies, the eager copy of ``fresh_buffer``) are not checked: one that
prints a scoped key of a recorded program would be counted under that
scope.

A program without these names (an older commit) gives nothing to read:
:func:`scope_map` and :func:`ring` return ``None`` and every reader here
then reads ``None``.  Busy time is the same union as
:mod:`bench.harness.trace`'s.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, Optional

from . import hlo
from . import trace as tr

#: the engine's conversions around the kernel: the per-launch wrap pad,
#: the resident layout's margin refresh, and its enter/exit
MARGIN = ("wfa.engine.wrap_pad", "wfa.engine.margin_refresh", "wfa.engine.layout")
#: Krylov's own vector passes: reductions and updates
KRYLOV = ("wfa.krylov.dot", "wfa.krylov.update")
KERNEL = ("wfa.kernel.stencil",)

#: an unscoped operation is named in the notes above this share of busy time
NOTE_SHARE = 0.001

_seen: list = []


def scope_map() -> Optional[Dict[tuple, str]]:
    """The program's map from instruction key to scope; ``None`` where the
    program has none.  Computed once per process: ``device_scopes()``
    compiles again."""
    if not _seen:
        try:
            from repro.engine.stats import device_scopes
        except ImportError:
            _seen.append(None)
        else:
            _seen.append(device_scopes())
    return _seen[0]


def ring() -> Optional[list]:
    """The program's span ring, ``(name, start_ns, end_ns, parent)`` on
    ``time.perf_counter_ns()``; ``None`` where the program keeps none."""
    try:
        from repro.engine.stats import spans
    except ImportError:
        return None
    return spans()


def key(ins: hlo.Instruction) -> tuple:
    """The key ``device_scopes()`` gives an instruction: its name and its
    result shapes, ``("fusion.6", ("f32[516,516,128]",))``."""
    return ins.name, tuple(f"{dt}[{','.join(map(str, dims))}]" for dt, dims in ins.result)


def split(trace: tr.Trace, scopes: Dict[tuple, str]):
    """Device busy seconds in the window by scope, averaged over the
    devices: ``(per_scope, unscoped, busy)``.  ``per_scope`` maps each
    scope to the union of its operations' intervals; ``unscoped`` maps
    ``"<opcode> <name>"`` of each operation the map does not know to its
    union.  The parts add up to ``busy`` where no two operations overlap."""
    lo, hi = trace.window()
    per: Dict[str, float] = defaultdict(float)
    off: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for d in trace.devices:
        groups: Dict[tuple, list] = defaultdict(list)
        for o in tr.ops(trace, d):
            scope = scopes.get(key(o.ins))
            name = (scope, None) if scope else (None, f"{o.ins.opcode} {o.ins.name}")
            groups[name].append((o.start, o.end))
        for (scope, name), iv in groups.items():
            t = tr.length(tr.union(tr.clip(iv, lo, hi))) / 1e9
            if scope:
                per[scope] += t
            else:
                off[name] += t
        busy += tr.length(tr._busy(trace, d, lo, hi)) / 1e9
    k = len(trace.devices)
    return ({s: t / k for s, t in per.items()}, {n: t / k for n, t in off.items()},
            busy / k)


def share(trace: tr.Trace, scopes: Dict[tuple, str], group) -> Optional[float]:
    """Percent of device busy time in the window in operations whose scope
    is one of ``group``; ``None`` where none of them ran."""
    lo, hi = trace.window()
    busy = picked = 0.0
    for d in trace.devices:
        busy += tr.length(tr._busy(trace, d, lo, hi))
        picked += tr.length(tr._busy(trace, d, lo, hi,
                                     lambda ins: scopes.get(key(ins)) in group))
    if picked == 0.0 or busy == 0.0:
        return None
    return 100.0 * picked / busy


def note_split(trace: tr.Trace, scopes: Dict[tuple, str]) -> str:
    """The split by scope as one line: seconds per scope, every unscoped
    operation above :data:`NOTE_SHARE` of busy time, and the sum beside
    busy time."""
    per, off, busy = split(trace, scopes)
    parts = [f"{s} {t:.6f} s" for s, t in sorted(per.items(), key=lambda x: -x[1])]
    big = [(n, t) for n, t in sorted(off.items(), key=lambda x: -x[1])
           if t > NOTE_SHARE * busy]
    rest = sum(off.values()) - sum(t for _, t in big)
    parts += [f"unscoped {n} {t:.6f} s" for n, t in big]
    parts.append(f"unscoped other {rest:.6f} s")
    total = sum(per.values()) + sum(off.values())
    return (", ".join(parts) + f"; sum {total:.6f} s of busy {busy:.6f} s "
            f"({100.0 * (total / busy - 1.0):+.4f} %)")


def scope_share(ctx, group, note: str) -> Optional[float]:
    """A share reader: ``group``'s percent of busy time, with the whole
    split written to ``ctx.notes[note]``."""
    scopes = scope_map() if ctx.trace is not None else None
    if scopes is None:
        return None
    ctx.notes[note] = note_split(ctx.trace, scopes)
    return share(ctx.trace, scopes, group)


def dispatch_us(ctx, name: str, note: str) -> Optional[float]:
    """A dispatch reader: host us per unit of the window inside the
    program's ``name`` spans that opened in the window, from the ring and
    the window's times alone (one clock).  ``ctx.notes[note]`` gets the
    spans' count and mean length, and their children's."""
    spans = ring()
    if spans is None or not ctx.window.units:
        return None
    lo, hi = ctx.window.start * 1e9, ctx.window.end * 1e9
    inside = [(e - s) / 1e3 for n, s, e, _ in spans if n == name and lo <= s < hi]
    if not inside:
        return None
    children = defaultdict(list)
    for n, s, e, parent in spans:
        if parent == name and lo <= s < hi:
            children[n].append((e - s) / 1e3)
    ctx.notes[note] = "; ".join(
        [f"{len(inside)} {name} spans in the window, "
         f"{statistics.mean(inside):.3f} us each"]
        + [f"{n} {statistics.mean(t):.3f} us each" for n, t in sorted(children.items())])
    return sum(inside) / len(ctx.window.units)

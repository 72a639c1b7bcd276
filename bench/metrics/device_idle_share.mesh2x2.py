"""Percent of the traced window in which no operation ran on a chip (1
minus the union of its busy intervals over the window), the mean over the
mesh's chips; the notes name the worst chip."""
from bench.harness import parsed
from bench.harness import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    idle = {d: 100.0 * (1.0 - tr.length(tr.union(tr.clip(
                [(s, e) for s, e, _ in ops], lo, hi))) / (hi - lo))
            for d, ops in sorted(parsed.device_ops(ctx.trace).items())}
    worst = max(idle, key=idle.get)
    ctx.notes["device_idle"] = f"worst chip {worst} {idle[worst]:.4f} %"
    return sum(idle.values()) / len(idle)

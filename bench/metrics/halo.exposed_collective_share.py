"""Percent of device busy time in which the halo exchange runs alone.

Per chip: the union of the intervals of the operations under the
program's ``wfa.halo.exchange`` scope (the ``ppermute`` transfers and the
slabs they send), less the union of every other operation's intervals on
that chip, over the chip's busy time; the mean over the chips.  An
exchange that a kernel or a repack overlaps is hidden and does not count.
The notes give each chip's share, the bytes a chip sends a launch and the
exchanges a chunk.
"""
from bench.harness import parsed, scopes
from bench.harness import trace as tr

EXCHANGE = ("wfa.halo.exchange", None)


def read(ctx):
    scope_map = scopes.scope_map() if ctx.trace is not None else None
    if scope_map is None:
        return None
    shares, seen = {}, False
    for d, groups in sorted(parsed.by_scope(ctx.trace, scope_map).items()):
        mine = groups.get(EXCHANGE, [])
        rest = tr.union(iv for g, ivs in groups.items() if g != EXCHANGE for iv in ivs)
        seen = seen or bool(mine)
        busy = tr.length(parsed.busy(groups))
        if busy > 0:
            shares[d] = 100.0 * tr.length(tr.minus(mine, rest)) / busy
    if not seen:
        return None
    k = ctx.info.get("time_tile", 1)
    steps = int(ctx.traffic.get("chunk_steps", 0))
    ctx.notes["halo_exchange"] = (
        "exposed by chip: " + ", ".join(f"{d} {s:.4f} %" for d, s in shares.items())
        + f"; {ctx.info.get('halo_bytes_per_launch')} bytes sent a chip a launch, "
        f"{steps // k + steps % k} exchanges a chunk")
    return sum(shares.values()) / len(shares)

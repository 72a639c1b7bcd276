"""The fused stencil kernel's share of its HBM roofline, in percent.

Over every launch in the traced window: the bytes of its operands (each
read once) and results (each written once), from the launch's own shapes,
at the chip's published HBM bandwidth, over the summed device time of the
launches.  A bytes bound: the 7-point update does about one operation per
byte and v5e publishes no fp32 vector peak.
"""
from bench.harness import trace


def read(ctx):
    if ctx.trace is None:
        return None
    r = trace.kernel_roofline(ctx.trace, ctx.peaks["hbm_bytes_per_s"])
    if r is None:
        return None
    # 8 operations per updated cell per step: six adds, two multiplies
    ops = 8 * ctx.info.get("time_tile", 1) * r["cells"]
    ctx.notes["fused_kernel"] = (
        f"{r['launches']} launches, {r['bytes'] / r['launches']:.0f} bytes and "
        f"{r['seconds'] / r['launches'] * 1e6:.1f} us each, "
        f"{ops / r['bytes']:.3f} operations per byte")
    return r["share"]

"""The fused stencil kernel's share of its HBM roofline on the mesh, in
percent.

Over every brick launch on every chip in the traced window, as
``bench.harness.trace.kernel_roofline`` counts them: the bytes of its
operands (each read once) and results (each written once), from the
launch's own shapes, at one chip's published HBM bandwidth, over the
summed device time of the launches.  A bytes bound: the 7-point update
does about one operation per byte and v5e publishes no fp32 vector peak.
"""
from bench.harness import hlo, parsed


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    moved = seconds = cells = 0.0
    launches = 0
    for ops in parsed.device_ops(ctx.trace).values():
        for start, end, ins in ops:
            if start >= lo and end <= hi and hlo.is_stencil_kernel(ins):
                moved += hlo.launch_bytes(ins)
                cells += sum(hlo.nbytes([(dt, dims)]) // hlo.DTYPE_BYTES[dt]
                             for dt, dims in ins.result if len(dims) >= 3)
                seconds += (end - start) / 1e9
                launches += 1
    if launches == 0 or seconds == 0.0:
        return None
    # 8 operations per updated cell per step: six adds, two multiplies
    ops = 8 * ctx.info.get("time_tile", 1) * cells
    ctx.notes["fused_kernel"] = (
        f"{launches} launches over {len(ctx.trace.devices)} chips, "
        f"{moved / launches:.0f} bytes and {seconds / launches * 1e6:.1f} us each, "
        f"{ops / moved:.3f} operations per byte")
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / seconds

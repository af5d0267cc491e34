"""prim.reduce_roofline: the reductions' share of their roofline.  The
least time the chip needs to read every input byte of the window's
calls at peak HBM bandwidth (a reduction of n f32 does n operations on
4n bytes: bandwidth bounds it), over the device's busy time in the
window."""


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if not tr or not work.get("bytes"):
        return None
    p = ctx["peaks"]
    ideal = max(work["bytes"] / p["hbm_bytes_per_s"],
                work["flops"] / p["bf16_flops"])
    return 100.0 * ideal / tr["busy_s"]

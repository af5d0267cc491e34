"""decode.step_roofline: the batched decode step's share of its
roofline.  The least time one step needs (``bench/flops.py``
``Decoder.decode_step_bytes``: the layers' and the output layer's bf16
weights, and the K and V of each live slot's earlier positions, read
once at peak HBM bandwidth; or its FLOPs at the bf16 peak, whichever
is longer), averaged over the window's decode steps, over the median
device time of the jitted ``decode`` program.  Reading a slot's dead
capacity is not needed, so it shows as a share that is missing."""

from bench import stats
from bench import trace as T


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if not tr or not work.get("decode_steps"):
        return None
    progs = T.programs(tr["events"], tr["plane"], T.PROGRAMS["decode"],
                       tr["lo"], tr["hi"])
    if not progs:
        return None
    p = ctx["peaks"]
    ideal = max(work["decode_bytes"] / p["hbm_bytes_per_s"],
                work["decode_flops"] / p["bf16_flops"])
    step_s = stats.median(e.end - e.start for e in progs) * 1e-9
    return 100.0 * ideal / work["decode_steps"] / step_s

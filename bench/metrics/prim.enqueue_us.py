"""prim.enqueue_us: median host time an engine takes to put a call's
programs on the device: the program's span ``repro.engine``
(``repro.obs``), in the traced window."""

from bench import program_spans as P
from bench import stats


def read(ctx):
    runs = P.spans(ctx, "repro.engine")
    if not runs:
        return None
    return stats.median(s.t1_ns - s.t0_ns for s in runs) * 1e-3

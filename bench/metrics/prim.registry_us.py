"""prim.registry_us: median self time of the op registry per call in
the traced window: the program's span ``repro.dispatch`` less its
``repro.engine`` child (``repro.obs``)."""

from bench import program_spans as P
from bench import stats


def read(ctx):
    calls = P.spans(ctx, "repro.dispatch")
    if not calls:
        return None
    inner: dict = {}
    for s in P.spans(ctx, "repro.engine"):
        inner[s.parent_id] = inner.get(s.parent_id, 0) + s.t1_ns - s.t0_ns
    return stats.median(c.t1_ns - c.t0_ns - inner.get(c.id, 0)
                        for c in calls) * 1e-3

"""prefill.decode_gap_ms: median time between the end of one decode
step on the device and the start of the next, over the pairs of steps
with no admission prefill between them: the scheduler's and the paged
store's work between two steps (``as_dense``, ``write_token``, the
picks' syncs), which every short answer waits on once a token."""

import bisect

from bench import stats
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    ev, plane, lo, hi = tr["events"], tr["plane"], tr["lo"], tr["hi"]
    steps = T.programs(ev, plane, T.PROGRAMS["decode"], lo, hi)
    admits = [e.start for e in T.programs(ev, plane, T.PROGRAMS["prefill"],
                                          lo, hi)]
    gaps = [b.start - a.end for a, b in zip(steps, steps[1:])
            if bisect.bisect(admits, a.end) == bisect.bisect(admits,
                                                             b.start)]
    return stats.median(gaps) * 1e-6 if gaps else None

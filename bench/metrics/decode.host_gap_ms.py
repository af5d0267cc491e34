"""decode.host_gap_ms: median time between the end of one decode step
on the device and the start of the next: the scheduler's and the paged
store's host work (``as_dense``, ``write_token``, the picks' syncs)."""

from bench import stats
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    progs = T.programs(tr["events"], tr["plane"], T.PROGRAMS["decode"],
                       tr["lo"], tr["hi"])
    gaps = T.gaps_between(progs)
    return stats.median(gaps) * 1e-6 if gaps else None

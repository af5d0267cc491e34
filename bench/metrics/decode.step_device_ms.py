"""decode.step_device_ms: median device time of one batched decode
step (the jitted ``decode`` program) in the traced window."""

from bench import stats
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    progs = T.programs(tr["events"], tr["plane"], T.PROGRAMS["decode"],
                       tr["lo"], tr["hi"])
    if not progs:
        return None
    return stats.median(e.end - e.start for e in progs) * 1e-6

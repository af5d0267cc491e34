"""prefill.device_ms_per_ktok: device time of the admission prefills
(the jitted ``prefill`` program) in the traced window, per thousand
prompt tokens admitted in it."""

from bench import trace as T


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if not tr or not work.get("prompt_tokens"):
        return None
    progs = T.programs(tr["events"], tr["plane"], T.PROGRAMS["prefill"],
                       tr["lo"], tr["hi"])
    if not progs:
        return None
    ms = sum(e.end - e.start for e in progs) * 1e-6
    return ms / (work["prompt_tokens"] / 1000.0)

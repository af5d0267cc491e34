"""The program's own spans (``repro.obs``) on the trace's clock, for
the per-layer readers.

The program times its spans with ``time.perf_counter_ns``, the clock
the driver's spans are taken on, so the offset ``bench/run.py``
``reduce_trace`` found for those (from the ``bench.anchor`` span)
moves these too: the window opens at ``ctx["trace"]["lo"]`` on the
trace's clock and at ``ctx["record"].t_open`` seconds on the host's.
A program without ``repro.obs`` records no spans, and every reader
built on this module then reads nothing.

That offset moves host spans onto the trace's clock, but the device's
timeline may sit apart from the host's by a skew of the profiler's
own, different in each run (up to ~2 ms on a TPU v5e).  So no reader
here compares a host instant with a device instant; ``call_idle``
uses the skew only to pair calls with their programs.
"""

from __future__ import annotations

import numpy as np

from bench import stats
from bench import trace as T

# Offsets searched for the skew between the host's and the device's
# timelines, and the share of programs that may fit no call.
SKEWS_NS = np.arange(-5_000_000, 5_000_001, 4_000)
UNPAIRED = 0.01


def spans(ctx, name: str) -> list:
    """The program's spans called ``name`` that lie inside the traced
    window, with ``t0_ns`` / ``t1_ns`` on the trace's clock."""
    tr, rec = ctx.get("trace"), ctx.get("record")
    if not tr or rec is None:
        return []
    try:
        from repro import obs
    except ImportError:
        return []
    offset = tr["lo"] - rec.t_open * 1e9
    out = []
    for s in obs.spans():
        t0, t1 = s.t0_ns + offset, s.t1_ns + offset
        if s.name == name and tr["lo"] <= t0 and t1 <= tr["hi"]:
            out.append(s._replace(t0_ns=t0, t1_ns=t1))
    return out


def call_idle(ctx) -> dict:
    """``{op: [ns, ...]}``: for each call but the window's last, the
    time the device idled in it, from the call's start (span
    ``repro.dispatch``) to the next call's, less the device time of
    the call's own programs.

    Both terms are durations, one on the host's clock and one on the
    device's, so the profiler's skew between the two timelines, which
    the ``bench.anchor`` offset does not remove, does not enter them.
    It enters only the pairing of calls with programs: each program
    belongs to the call in whose stretch, from its engine run's start
    to the next call's start, it runs whole, once that stretch is
    moved by the skew.  The skew is taken as the middle of the run of
    offsets in ``SKEWS_NS`` that place the most programs so (of two
    such runs, the one nearer zero).  This holds for a caller that
    reads each result back before its next call, as the reduction
    driver does; where more than ``UNPAIRED`` of the window's programs
    fit no call, nothing is read.
    """
    calls = sorted(spans(ctx, "repro.dispatch"), key=lambda s: s.t0_ns)
    if len(calls) < 2:
        return {}
    tr = ctx["trace"]
    engine_t0 = {s.parent_id: s.t0_ns for s in spans(ctx, "repro.engine")}
    t0 = np.array([c.t0_ns for c in calls])
    first = np.array([engine_t0.get(c.id, c.t0_ns) for c in calls])
    last = np.append(t0[1:], tr["hi"])
    mods = sorted((e.start, e.end) for e in tr["events"]
                  if e.plane == tr["plane"] and e.line == T.MODULES_LINE)
    if not mods:
        return {}
    start, end = np.array(mods).T

    def owner(skew):
        k = np.searchsorted(first + skew, start, side="right") - 1
        fits = (k >= 0) & (end <= last[np.maximum(k, 0)] + skew)
        return np.where(fits, k, -1)

    placed = np.array([np.count_nonzero(owner(d) >= 0) for d in SKEWS_NS])
    best = np.flatnonzero(placed == placed.max())
    runs = np.split(best, np.flatnonzero(np.diff(best) > 1) + 1)
    k = owner(min((SKEWS_NS[(r[0] + r[-1]) // 2] for r in runs), key=abs))
    inside = (start >= tr["lo"]) & (start <= tr["hi"])
    if np.count_nonzero(inside & (k < 0)) > UNPAIRED * inside.sum():
        return {}
    busy = np.bincount(k[k >= 0], weights=(end - start)[k >= 0],
                       minlength=len(calls))
    out: dict = {}
    for c, gap in zip(calls, np.diff(t0) - busy[:-1]):
        out.setdefault(c.attrs.get("op"), []).append(float(gap))
    return out


def median_call_idle_us(ctx, op: str):
    """Median of ``call_idle`` over the calls of ``op``, in µs."""
    gaps = call_idle(ctx).get(op) if ctx.get("trace") else None
    return stats.median(gaps) * 1e-3 if gaps else None

"""Reduce a profiler trace to device busy time, idle share, device
time per program and the idle gaps between programs.

A TPU trace (``jax.profiler``, read with ``ProfileData``) has one plane
per chip, ``/device:TPU:<i>``, whose line ``XLA Modules`` holds one
event per executed program (named ``jit_<fn>(<hash>)``) and whose line
``XLA Ops`` holds one event per HLO op inside them (named by the op's
HLO text, ``%name = ...``).  Host planes share the trace's time base,
so spans the benchmark records with ``TraceAnnotation`` line up with
device events.  Times are in nanoseconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

# Device programs by what they do, matched against module names with
# the hash stripped.  The serving loop's jitted steps are named by the
# functions ``ContinuousServer`` jits (``prefill``, ``decode``).
PROGRAMS = {
    "decode": re.compile(r"^jit_decode$"),
    "prefill": re.compile(r"^jit_prefill$"),
}


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float      # ns
    end: float        # ns


def module_name(name: str) -> str:
    """``jit_dot_general(5123...)`` -> ``jit_dot_general``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%multiply_reduce_fusion = f32[] fusion(...)`` ->
    ``multiply_reduce_fusion``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def load(log_dir: str, host_spans: str = "bench.") -> list:
    """Events of the device planes' module and op lines, and of host
    spans whose names start with ``host_spans``, from the newest
    ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return events_from(ProfileData.from_file(paths[-1]), host_spans)


def events_from(profile, host_spans: str = "bench.") -> list:
    out = []
    for plane in profile.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(host_spans):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns),
                                 float(e.start_ns) + float(e.duration_ns)))
    return out


def device_planes(events) -> list:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns them sorted, disjoint."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events, plane: str, lo: float, hi: float) -> float:
    """Union of the op intervals of ``plane`` inside [lo, hi]."""
    ops = [(e.start, e.end) for e in events
           if e.plane == plane and e.line == OPS_LINE]
    return sum(e - s for s, e in union(clip(ops, lo, hi)))


def idle_pct(tr) -> float | None:
    """Share of a reduced trace's window (``bench/run.py``
    ``reduce_trace``) in which no operation ran on the device."""
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def programs(events, plane: str, pattern, lo=float("-inf"),
             hi=float("inf")) -> list:
    """Module events of ``plane`` whose stripped name matches
    ``pattern`` and that start inside [lo, hi], in time order."""
    pat = re.compile(pattern) if isinstance(pattern, str) else pattern
    return sorted((e for e in events
                   if e.plane == plane and e.line == MODULES_LINE
                   and lo <= e.start <= hi
                   and pat.match(module_name(e.name))),
                  key=lambda e: e.start)


def gaps_between(progs) -> list:
    """Time from the end of each program to the start of the next."""
    return [b.start - a.end for a, b in zip(progs, progs[1:])]


def op_totals(events, plane: str, lo: float, hi: float) -> dict:
    """Device seconds per ``module/op`` inside [lo, hi]."""
    mods = sorted(((e.start, e.end, module_name(e.name)) for e in events
                   if e.plane == plane and e.line == MODULES_LINE))
    out: dict = {}
    j = 0
    for e in sorted((e for e in events
                     if e.plane == plane and e.line == OPS_LINE),
                    key=lambda e: e.start):
        if e.end <= lo or e.start >= hi:
            continue
        while j < len(mods) and mods[j][1] < e.start:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= e.start \
            else "?"
        key = f"{mod}/{op_name(e.name)}"
        out[key] = out.get(key, 0.0) + (min(e.end, hi)
                                        - max(e.start, lo)) * 1e-9
    return out


def idle_gaps(events, plane: str, lo: float, hi: float) -> list:
    """``[(label, seconds)]``: each idle stretch of ``plane`` inside
    [lo, hi], labelled by the host span that covers its middle (the
    innermost, that is the latest to start), or ``"?"``."""
    ops = [(e.start, e.end) for e in events
           if e.plane == plane and e.line == OPS_LINE]
    busy = union(clip(ops, lo, hi))
    spans = sorted((e for e in events if not DEVICE_PLANE.match(e.plane)),
                   key=lambda e: e.start)
    starts = [sp.start for sp in spans]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        label = "?"
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i].end >= mid:
                label = spans[i].name
                break
        out.append((label, (e - s) * 1e-9))
    return out


def top(pairs, k: int = 10) -> list:
    return [list(p) for p in sorted(pairs, key=lambda p: -p[1])[:k]]


def gap_totals(gaps) -> list:
    """Idle seconds summed by label."""
    out: dict = {}
    for label, s in gaps:
        out[label] = out.get(label, 0.0) + s
    return list(out.items())

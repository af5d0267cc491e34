"""The trace reduction (bench/trace.py) on a small recorded trace: device
events of eager reduce_sum / squared_sum calls on a TPU v5 lite, with
the host's call frames as spans."""

import json
import os

import pytest

from bench import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e_reduce.json")
PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        raw = json.load(f)["events"]
    return [T.Event(e["plane"], e["line"], e["name"], e["start"], e["end"])
            for e in raw]


def test_union_merges_overlaps_and_touching_intervals():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]
    assert T.union([]) == []
    assert T.union([(0, 10), (2, 3)]) == [(0, 10)]


def test_clip_keeps_only_the_window():
    assert T.clip([(0, 4), (5, 7), (9, 12)], 3, 10) == [
        (3, 4), (5, 7), (9, 10)]


def test_busy_is_the_union_of_op_intervals(events):
    ops = [(e.start, e.end) for e in events if e.line == T.OPS_LINE]
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    busy = T.busy_ns(events, PLANE, lo, hi)
    # The recorded ops never overlap, so the union is their sum.
    assert busy == pytest.approx(sum(e - s for s, e in ops))
    assert 0 < busy < hi - lo
    # Half the window holds at most what lies inside it.
    assert T.busy_ns(events, PLANE, lo, (lo + hi) / 2) < busy


def test_device_planes_and_module_names(events):
    assert T.device_planes(events) == [PLANE]
    names = {T.module_name(e.name) for e in events
             if e.line == T.MODULES_LINE}
    assert names == {"jit_dot_general", "jit_broadcast_in_dim",
                     "jit_convert_element_type"}
    assert T.op_name("%multiply_reduce_fusion = f32[] fusion(x)") == \
        "multiply_reduce_fusion"


def test_programs_match_by_pattern_in_time_order(events):
    dots = T.programs(events, PLANE, r"^jit_dot_general$")
    assert len(dots) == 12
    assert all(a.start < b.start for a, b in zip(dots, dots[1:]))
    # 2^28 contractions take ~2.8 ms on the device, 2^16 ones far less.
    big = [e for e in dots if e.end - e.start > 2e6]
    assert len(big) == 6
    gaps = T.gaps_between(dots)
    assert len(gaps) == 11 and all(g > 0 for g in gaps)
    assert T.programs(events, PLANE, T.PROGRAMS["decode"]) == []


def test_op_totals_attribute_ops_to_their_module(events):
    tot = T.op_totals(events, PLANE, float("-inf"), float("inf"))
    assert set(tot) == {"jit_dot_general/multiply_reduce_fusion",
                        "jit_broadcast_in_dim/broadcast_in_dim.1"}
    ops = [e for e in events if e.line == T.OPS_LINE]
    assert sum(tot.values()) == pytest.approx(
        sum(e.end - e.start for e in ops) * 1e-9)


def test_idle_gaps_cover_the_rest_and_take_the_covering_span(events):
    ops = [(e.start, e.end) for e in events if e.line == T.OPS_LINE]
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    gaps = T.idle_gaps(events, PLANE, lo, hi)
    idle = sum(s for _, s in gaps)
    assert idle * 1e9 + T.busy_ns(events, PLANE, lo, hi) == \
        pytest.approx(hi - lo)
    labels = {lab for lab, _ in gaps}
    assert labels <= {"?", "bench.reduce_sum", "bench.squared_sum"}
    assert labels & {"bench.reduce_sum", "bench.squared_sum"}
    totals = dict(T.gap_totals(gaps))
    assert sum(totals.values()) == pytest.approx(idle)
    assert len(T.top(list(totals.items()), 2)) <= 2


def test_events_from_a_profile_keep_device_lines_and_bench_spans():
    from jax.profiler import ProfileData
    text = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(42)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = f32[] x" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.anchor" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction" } }
}
"""
    ev = T.events_from(ProfileData.from_text_proto(text))
    by = {(e.line, e.name): e for e in ev}
    assert set(by) == {("XLA Modules", "jit_decode(42)"),
                       ("XLA Ops", "%fusion.3 = f32[] x"),
                       ("main", "bench.anchor")}
    dec = by[("XLA Modules", "jit_decode(42)")]
    assert dec.end - dec.start == pytest.approx(5000)
    assert T.programs(ev, "/device:TPU:0", T.PROGRAMS["decode"]) == [dec]

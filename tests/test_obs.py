"""Spans and counters of the program (repro.obs) and where the op
registry records them (repro.core.dispatch)."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import dispatch, integration


@pytest.fixture(autouse=True)
def clean():
    obs.reset()
    yield
    obs.reset()


def _xplane_events(log_dir, prefix="repro."):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns) for e in line.events
                    if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def test_spans_record_nothing_while_no_profile_records():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("a", k=1) as sp:
        sp.set(more=2)
        with obs.span("b"):
            pass
    float(integration.reduce_sum(jnp.ones(64)))
    assert obs.spans() == []
    assert obs.span("a") is obs.span("b")


def test_nested_spans_carry_parent_and_call_id(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with obs.span("outer", op="x") as sp:
                with obs.span("mid"):
                    with obs.span("inner"):
                        pass
                sp.set(engine="e")
    assert not jax.profiler.TraceAnnotation.is_enabled()
    got = obs.spans()
    assert [s.name for s in got] == ["inner", "mid", "outer"] * 2
    for inner, mid, outer in (got[:3], got[3:]):
        assert outer.parent_id is None and outer.call_id == outer.id
        assert mid.parent_id == outer.id and inner.parent_id == mid.id
        assert inner.call_id == mid.call_id == outer.id
        assert outer.t0_ns <= mid.t0_ns <= inner.t0_ns
        assert inner.t1_ns <= mid.t1_ns <= outer.t1_ns
        assert outer.attrs == {"op": "x", "engine": "e"}
    assert got[2].call_id != got[5].call_id


def test_spans_are_host_events_at_a_constant_offset(tmp_path):
    x = jnp.ones(256)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            float(integration.reduce_sum(x))
            float(integration.squared_sum(x))
    mine = sorted(obs.spans(), key=lambda s: s.t0_ns)
    assert [s.name for s in mine] == ["repro.dispatch",
                                      "repro.engine"] * 6
    calls = [s for s in mine if s.name == "repro.dispatch"]
    assert [s.attrs["op"] for s in calls] == ["reduce_sum",
                                              "squared_sum"] * 3
    assert all(s.attrs["engine"] == "mma" and s.attrs["n"] == 256
               and s.attrs["bytes"] == 1024 for s in calls)
    traced = _xplane_events(str(tmp_path))
    assert [n for n, _ in traced] == [s.name for s in mine]
    offsets = [t - s.t0_ns for (_, t), s in zip(traced, mine)]
    assert max(offsets) - min(offsets) < 2e6


def test_counters_count_calls_and_bytes_per_engine():
    x = jnp.ones(1000, jnp.float32)
    float(integration.reduce_sum(x))
    float(integration.reduce_sum(x, method="vpu"))
    float(integration.squared_sum(x))
    float(integration.squared_sum(x))
    c = obs.counters()
    assert c["dispatch.calls"] == {("reduce_sum", "mma"): 1,
                                   ("reduce_sum", "vpu"): 1,
                                   ("squared_sum", "mma"): 2}
    assert c["dispatch.bytes"] == {("reduce_sum", "mma"): 4000,
                                   ("reduce_sum", "vpu"): 4000,
                                   ("squared_sum", "mma"): 8000}
    assert "dispatch.fallbacks" not in c
    obs.reset()
    assert obs.counters() == {}


def test_counters_see_a_fallback():
    x = jnp.ones((4, 8))
    # A flatten-only engine cannot serve a per-row statistic.
    assert dispatch.resolve_method("reduce_sum", x, "pallas",
                                   axis=(1,)) == "vpu"
    assert dispatch.resolve_method("reduce_sum", x, "mma",
                                   axis=(1,)) == "mma"
    assert obs.counters() == {
        "dispatch.fallbacks": {("reduce_sum", "pallas", "vpu"): 1}}


def test_execute_counts_nothing():
    from repro.core import autotune
    plan = autotune.ReductionPlan(method="vpu")
    got = dispatch.execute("reduce_sum", jnp.arange(8.0), plan)
    assert float(got) == 28.0
    assert obs.counters() == {}


def test_a_measured_sweep_inside_a_call_counts_one_served_call(
        monkeypatch, tmp_path):
    from repro.core import autotune

    def sweep(n, dtype, *, op, **_):
        # The auto path's plan lookup times its candidates on this
        # host before it returns the winner.
        for method in ("vpu", "mma"):
            autotune.measure_cost(autotune.ReductionPlan(method=method),
                                  n, dtype, iters=2, warmup=1, op=op)
        return autotune.ReductionPlan(method="vpu")

    monkeypatch.setattr(autotune, "get_plan", sweep)
    x = jnp.ones(256)
    with jax.profiler.trace(str(tmp_path)):
        assert float(integration.reduce_sum(x, method="auto")) == 256.0
    assert obs.counters() == {
        "dispatch.calls": {("reduce_sum", "vpu"): 1},
        "dispatch.bytes": {("reduce_sum", "vpu"): 1024}}
    # The candidates' runs are no engine runs of the call.
    assert [s.name for s in obs.spans()] == ["repro.engine",
                                             "repro.dispatch"]


def test_a_jitted_call_names_its_scope_and_records_no_span(tmp_path):
    x = jnp.ones(512)
    with jax.profiler.trace(str(tmp_path)):
        lowered = jax.jit(lambda v: integration.reduce_sum(v)).lower(x)
    assert "reduce_sum.mma" in lowered.as_text(debug_info=True)
    assert obs.spans() == []
    # A trace is no call served; the counters count eager calls.
    assert obs.counters() == {}


def test_only_a_traced_call_opens_a_named_scope(monkeypatch):
    scopes = []
    real = jax.named_scope

    def watch(name):
        scopes.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", watch)
    x = jnp.ones(512)
    assert float(integration.reduce_sum(x)) == 512.0
    assert float(integration.squared_sum(x)) == 512.0
    assert scopes == []
    jax.jit(lambda v: integration.squared_sum(v)).lower(x)
    assert scopes == ["squared_sum.mma"]

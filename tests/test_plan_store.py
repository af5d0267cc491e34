"""Fleet plan-store tests (ISSUE-8): versioned schema, atomic +
locked + merge-on-save persistence, shared warmup, and the background
sweep worker.

The store is written by many processes (serving fleet, elastic
trainers), so the acceptance surface here is concurrency-shaped:

  * the JSON document is versioned — the legacy flat form still
    loads, a *future* schema version is refused instead of
    half-parsed;
  * ``save`` is atomic (temp file + ``os.replace``), serialised by an
    advisory file lock, and merges the on-disk plans first — the
    two-interleaved-writers regression proves neither writer's plans
    are dropped;
  * the merge rule prefers measured over model, then lower cost;
  * ``warmup`` collapses a ragged hot set onto its bucket caps and
    counts the tuning events;
  * ``SweepWorker`` upgrades model plans to measured off the hot path
    and shuts down deadlock-free even with a sweep in flight.
"""

import json
import logging
import os
import time

import jax.numpy as jnp
import pytest

from repro.core import autotune
from repro.core.autotune import PlanRegistry, ReductionPlan


def _plan(source="model", cost=10.0, method="vpu"):
    return ReductionPlan(method=method, source=source, cost=cost)


# ---------------------------------------------------------------------
# Versioned schema
# ---------------------------------------------------------------------

def test_versioned_document_round_trip(tmp_path):
    reg = PlanRegistry()
    reg.put("reduce_sum|1024|float32|cpu", _plan())
    store = tmp_path / "plans.json"
    reg.save(str(store))
    raw = json.loads(store.read_text())
    assert raw["version"] == autotune.SCHEMA_VERSION
    assert "reduce_sum|1024|float32|cpu" in raw["plans"]
    back = PlanRegistry.load(str(store))
    assert back.items() == reg.items()
    assert back.path == str(store)


def test_legacy_flat_form_still_loads(tmp_path):
    store = tmp_path / "legacy.json"
    store.write_text(json.dumps(
        {"reduce_sum|2048|float32|cpu": _plan().to_dict()}))
    back = PlanRegistry.load(str(store))
    assert len(back) == 1
    key, plan = back.items()[0]
    assert key == "reduce_sum|2048|float32|cpu"
    assert plan.method == "vpu"


def test_future_schema_version_refused(tmp_path):
    store = tmp_path / "future.json"
    store.write_text(json.dumps({"version": 99, "plans": {}}))
    with pytest.raises(ValueError, match="99"):
        PlanRegistry.load(str(store))
    # a versioned document with a junk version is refused too
    store.write_text(json.dumps({"plans": {}}))
    with pytest.raises(ValueError):
        PlanRegistry.load(str(store))


# ---------------------------------------------------------------------
# Atomic, locked, merge-on-save persistence
# ---------------------------------------------------------------------

def test_save_is_atomic_no_temp_residue(tmp_path):
    reg = PlanRegistry()
    reg.put("reduce_sum|1024|float32|cpu", _plan())
    store = tmp_path / "plans.json"
    for _ in range(3):
        reg.save(str(store))
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == ["plans.json", "plans.json.lock"]
    assert json.loads(store.read_text())["version"] == 1


def test_interleaved_writers_both_survive(tmp_path):
    """The torn-store regression: two registries pointed at one file,
    saving in alternation — a naive write-what-I-have save would drop
    the other writer's plans on every save."""
    store = str(tmp_path / "shared.json")
    a = PlanRegistry(store)
    b = PlanRegistry(store)
    a.put("reduce_sum|1024|float32|cpu", _plan(cost=1.0))
    a.save()
    b.put("reduce_sum|4096|float32|cpu", _plan(cost=2.0))
    b.save()                       # must merge a's plan, not clobber
    a.put("scan|1024|float32|cpu", _plan(cost=3.0))
    a.save()                       # must merge b's plan, not clobber
    final = PlanRegistry.load(store)
    assert sorted(k for k, _ in final.items()) == [
        "reduce_sum|1024|float32|cpu",
        "reduce_sum|4096|float32|cpu",
        "scan|1024|float32|cpu",
    ]


def test_merge_prefers_measured_then_lower_cost():
    reg = PlanRegistry()
    reg.put("k1", _plan(source="model", cost=5.0))
    reg.put("k2", _plan(source="measured", cost=50.0, method="mma"))
    other = PlanRegistry()
    other.put("k1", _plan(source="measured", cost=99.0, method="mma"))
    other.put("k2", _plan(source="model", cost=1.0))
    other.put("k3", _plan())
    adopted = reg.merge(other)
    assert adopted == 2            # k1 upgraded, k3 new; k2 kept
    plans = dict(reg.items())
    assert plans["k1"].source == "measured"
    assert plans["k2"].source == "measured"
    # same source: lower cost wins
    reg2 = PlanRegistry()
    reg2.put("k", _plan(cost=9.0))
    o2 = PlanRegistry()
    o2.put("k", _plan(cost=4.0))
    assert reg2.merge(o2) == 1
    assert dict(reg2.items())["k"].cost == 4.0


def test_reload_merges_disk_into_memory(tmp_path):
    store = str(tmp_path / "shared.json")
    peer = PlanRegistry(store)
    peer.put("reduce_sum|1024|float32|cpu", _plan(source="measured"))
    peer.save()
    mine = PlanRegistry(store)
    mine.put("scan|1024|float32|cpu", _plan())
    assert mine.reload() == 1
    assert len(mine) == 2


def test_bind_default_registry_round_trip(tmp_path,
                                          fresh_plan_registry):
    store = str(tmp_path / "fleet.json")
    reg = autotune.bind_default_registry(store)
    autotune.get_plan(1500, jnp.float32)       # default registry
    reg.save()
    autotune.reset_default_registry()
    reg2 = autotune.bind_default_registry(store)
    assert "reduce_sum|2048|float32|cpu" in dict(reg2.items())


# ---------------------------------------------------------------------
# invalidate_mesh / mesh_signatures
# ---------------------------------------------------------------------

def test_invalidate_mesh_suffix_exact():
    reg = PlanRegistry()
    keys = [
        "reduce_sum|1024|float32|cpu",
        "reduce_sum|1024|float32|cpu|mesh:data8",
        "reduce_sum|1024|float32|cpu|mma+vpu|mesh:data8",
        "reduce_sum|1024|float32|cpu|mesh:data4.model2",
    ]
    for k in keys:
        reg.put(k, _plan())
    assert reg.mesh_signatures() == ("data4.model2", "data8")
    dead = reg.invalidate_mesh("data8")
    assert dead == (keys[1], keys[2])
    left = {k for k, _ in reg.items()}
    assert left == {keys[0], keys[3]}
    # unknown / empty signatures are no-ops
    assert reg.invalidate_mesh("data16") == ()
    assert reg.invalidate_mesh(None) == ()


# ---------------------------------------------------------------------
# Shared warmup
# ---------------------------------------------------------------------

def test_warmup_collapses_ragged_hot_set(fresh_plan_registry):
    reg = fresh_plan_registry
    out = autotune.warmup(("reduce_sum", "squared_sum"),
                          [1000, 1024, 1700, 2048],
                          registry=reg)
    # 4 ragged shapes x 2 ops -> 2 caps x 2 ops = 4 keys, all tuned
    assert out["resolved"] == 4 and out["tuned"] == 4
    assert len(out["keys"]) == 4 and len(reg) == 4
    again = autotune.warmup(("reduce_sum", "squared_sum"),
                            [1000, 1024, 1700, 2048],
                            registry=reg)
    assert again["resolved"] == 4 and again["tuned"] == 0


def test_warmup_accepts_per_shape_dtype(fresh_plan_registry):
    reg = fresh_plan_registry
    out = autotune.warmup("reduce_sum",
                          [(1000, jnp.float32), (1000, jnp.bfloat16)],
                          registry=reg)
    assert out["tuned"] == 2
    keys = {k for k, _ in reg.items()}
    assert "reduce_sum|1024|float32|cpu" in keys
    assert "reduce_sum|1024|bfloat16|cpu" in keys


# ---------------------------------------------------------------------
# Background sweep worker
# ---------------------------------------------------------------------

def test_sweep_worker_upgrades_model_plan_off_hot_path(
        fresh_plan_registry):
    reg = fresh_plan_registry
    with autotune.SweepWorker(reg, iters=1) as worker:
        reg.sweep_worker = worker
        n = 512                      # tiny: the measured sweep is fast
        t0 = time.perf_counter()
        plan = autotune.get_plan(n, jnp.float32, registry=reg)
        cold_s = time.perf_counter() - t0
        assert plan.source == "model"        # served immediately
        assert cold_s < 5.0                  # never blocks on measure
        assert worker.drain(timeout_s=120.0)
        key = autotune.plan_key("reduce_sum", n, jnp.float32)
        upgraded = reg.get(key)
        assert upgraded is not None and upgraded.source == "measured"
        assert worker.upgraded == 1 and worker.failed == 0
        # a later identical resolution serves the measured plan
        assert autotune.get_plan(n, jnp.float32,
                                 registry=reg).source == "measured"


def test_sweep_worker_counts_and_logs_failures(fresh_plan_registry,
                                               caplog):
    """A sweep that raises keeps the model plan serving, but the
    failure is counted in ``failed`` and logged, never swallowed."""
    reg = fresh_plan_registry
    with caplog.at_level(logging.ERROR, logger="repro.core.autotune"):
        with autotune.SweepWorker(reg, iters=1) as worker:
            assert worker.submit("no_such_op|512|float32|cpu", dict(
                n=512, dtype=jnp.float32, op="no_such_op"))
            assert worker.drain(timeout_s=60.0)
            assert worker.failed == 1 and worker.upgraded == 0
    assert any("no_such_op" in r.getMessage() and r.exc_info
               for r in caplog.records)


def test_sweep_worker_dedups_and_close_never_deadlocks(
        fresh_plan_registry):
    reg = fresh_plan_registry
    worker = autotune.SweepWorker(reg, iters=1)
    spec = dict(n=512, dtype=jnp.float32, op="reduce_sum")
    key = autotune.plan_key("reduce_sum", 512, jnp.float32)
    assert worker.submit(key, dict(spec))
    assert not worker.submit(key, dict(spec))   # in-flight dedup
    t0 = time.perf_counter()
    worker.close(timeout_s=10.0)    # sweep may be mid-measure: the
    closed_s = time.perf_counter() - t0  # cancel hook must fire
    assert closed_s < 30.0
    assert not worker.submit(key, dict(spec))   # closed: refuses
    worker.close()                               # idempotent


def test_sweep_worker_ignores_foreign_backend(fresh_plan_registry):
    """get_plan only enqueues sweeps the local backend can measure."""
    reg = fresh_plan_registry
    with autotune.SweepWorker(reg) as worker:
        reg.sweep_worker = worker
        autotune.get_plan(1024, jnp.float32, registry=reg,
                          backend="tpu")
        assert worker.pending() == 0

"""Dispatch-layer overhead microbenchmark.

The TC-op registry (repro.core.dispatch) sits between every framework
hook and its engine.  This driver quantifies what that indirection
costs:

  dispatch/eager/...     per-call cost of the full hook path (context
                         build + capability check + engine run) vs
                         calling the engine directly — the un-jitted
                         worst case, where the Python layer runs every
                         call;
  dispatch/jit/...       the same under jit, where dispatch happens
                         once at trace time and the steady state is
                         pure compiled code (the production posture —
                         the overhead must vanish here);
  dispatch/auto/...      the auto path with a warm plan registry (one
                         dict lookup + engine run) vs explicit method;
  dispatch/decision_us   the dispatch decision alone (registry lookup,
                         context, capability, plan fetch) with the
                         engine run stubbed out.

Run:  PYTHONPATH=src:. python benchmarks/bench_dispatch.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_us
from repro.core import autotune, dispatch
from repro.core import integration as ci
from repro.core import reduction as R

N = 1 << 16


def run():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(N).astype(np.float32))

    # ---- eager: full hook path vs direct engine call
    direct = time_us(R.tc_sum, x)
    hooked = time_us(lambda v: ci.reduce_sum(v, method="mma"), x)
    emit("dispatch/eager/direct_engine", direct, "tc_sum")
    emit("dispatch/eager/via_registry", hooked,
         f"overhead_us={hooked - direct:.2f}")

    # ---- jit: dispatch happens at trace time only
    jdirect = jax.jit(lambda v: R.tc_contract(v, jnp.ones_like(v)))
    jhooked = jax.jit(lambda v: ci.reduce_sum(v, method="mma"))
    d = time_us(jdirect, x)
    h = time_us(jhooked, x)
    emit("dispatch/jit/direct_engine", d, "tc_contract")
    emit("dispatch/jit/via_registry", h,
         f"overhead_us={h - d:.2f};expect~0")

    # ---- auto path with a warm registry (plan-cache hit per call)
    autotune.reset_default_registry()
    ci.reduce_sum(x, method="auto")          # warm the plan cache
    a = time_us(lambda v: ci.reduce_sum(v, method="auto"), x)
    emit("dispatch/auto/warm_registry", a,
         f"vs_explicit_us={a - hooked:.2f}")

    # ---- the decision alone: stub the engine runner out
    spec = dispatch.op_spec("reduce_sum")
    stub = dispatch.OpSpec(
        name="reduce_sum", family=spec.family,
        engines=tuple(
            dispatch.EngineSpec(
                e.name, lambda v, plan, **kw: v,
                multi_device_safe=e.multi_device_safe,
                axis_subsets=e.axis_subsets, sweep=e.sweep)
            for e in spec.engines),
        reference=spec.reference)
    dispatch.register(stub)
    try:
        dec = time_us(lambda v: dispatch.dispatch(
            "reduce_sum", v, method="mma"), x, iters=200)
        emit("dispatch/decision_us", dec, "engine_run_stubbed")
        deca = time_us(lambda v: dispatch.dispatch(
            "reduce_sum", v, method="auto"), x, iters=200)
        emit("dispatch/decision_auto_us", deca,
             "plan_lookup+capability+context")
    finally:
        dispatch.register(spec)              # restore the real op

    # ---- axis-aware batched reduction: registry path vs raw jnp
    xb = jnp.asarray(rng.standard_normal((64, 1024))
                     .astype(np.float32))
    jb = jax.jit(lambda v: ci.reduce_sum(v, axis=-1, method="mma"))
    jv = jax.jit(lambda v: jnp.sum(v, axis=-1))
    emit("dispatch/axis/mma_lastdim", time_us(jb, xb), "registry path")
    emit("dispatch/axis/jnp_sum", time_us(jv, xb), "baseline")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()

"""Tests for the TC-op registry dispatch layer (ISSUE-3 surface).

Registry-driven by construction: the op list, each op's engines, its
aliases, and its reference oracle are all read off
``repro.core.dispatch`` — adding an op or engine to the registry
automatically widens this suite.

  * equivalence: every op x every declared engine (and alias) matches
    the op's reference oracle, in f32 and bf16, under the precision
    contract's tolerances — plain, under ``jit``, and (for the batched
    engines) under ``vmap``;
  * axis-aware reductions: ``reduce_sum``/``reduce_mean``/
    ``squared_sum`` with int/tuple/negative axes and keepdims match
    ``jnp.sum``/``mean`` in f32;
  * capability structure: illegal engines raise ``ValueError`` (the
    expert_counts 'pallas' silent-misroute regression), multi-device
    predicates restrict the legal set, and the auto path only ever
    executes a legal engine;
  * the one-executor contract: ``autotune.execute_plan`` runs every op
    family through the registry runners.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, dispatch
from repro.core import integration as ci

N = 4_097  # odd, non-tile-multiple


def _op_inputs(op: str, dtype=jnp.float32, seed: int = 0):
    """A representative (x, op_kwargs) problem for one registered op."""
    rng = np.random.default_rng(seed)
    if op == "expert_counts":
        onehot = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 300)]
        return jnp.asarray(onehot).astype(dtype), {}
    x = jnp.asarray(rng.normal(size=N).astype(np.float32)).astype(dtype)
    if op == "masked_mean":
        mask = jnp.asarray((rng.random(N) > 0.5).astype(np.float32))
        return x, {"mask": mask.astype(dtype)}
    if op == "segment_sum":
        ids = jnp.asarray(rng.integers(0, 37, N).astype(np.int32))
        return x, {"segment_ids": ids, "num_segments": 37}
    if op in ("scan", "masked_cumsum"):
        return x, {"axis": -1, "inclusive": True}
    if op == "attention":
        # Small enough that the fused interpret-mode kernel stays fast,
        # non-trivial on every axis: batch, GQA groups, KV heads.
        def t(*shape):
            return jnp.asarray(rng.normal(size=shape)
                               .astype(np.float32)).astype(dtype)
        return t(2, 24, 2, 2, 16), {
            "k": t(2, 24, 2, 16), "v": t(2, 24, 2, 16),
            "qpos": jnp.arange(24, dtype=jnp.int32),
            "causal": True, "scale": 0.25}
    if op == "norm_matmul":
        # The full surface in one problem: non-lane-multiple d/dout,
        # gate + bias + act — every engine must agree on the pair
        # act(xh @ w_gate) * (xh @ w + bias).
        def t(*shape):
            return jnp.asarray(rng.normal(size=shape)
                               .astype(np.float32)).astype(dtype)
        return t(6, 40), {
            "w": t(40, 24), "scale": t(40) * 0.1,
            "w_gate": t(40, 24), "bias": t(24), "act": "silu"}
    return x, {}


def _tol(dtype, n=N):
    scale = float(np.sqrt(n))
    if dtype == jnp.bfloat16:
        return dict(rtol=2e-2, atol=2e-2 * scale)
    return dict(rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op", dispatch.ops())
def test_every_engine_matches_oracle(op, dtype, fresh_plan_registry):
    spec = dispatch.op_spec(op)
    x, kw = _op_inputs(op, dtype)
    want = np.asarray(spec.reference(x, **kw), dtype=np.float64)
    spellings = spec.engine_names() + tuple(spec.aliases or ()) + ("auto",)
    for method in spellings:
        eng = spec.engine(method)
        if eng is not None and \
                dispatch._policy_reason(eng, None) is not None:
            # Policy-gated engine (the dd family): unreachable without
            # an explicit accum_dtype policy — refusal IS the contract.
            with pytest.raises(ValueError, match="policy|accum|pair"):
                dispatch.dispatch(op, x, method=method, **kw)
            continue
        got = np.asarray(dispatch.dispatch(op, x, method=method, **kw))
        np.testing.assert_allclose(got, want, err_msg=f"{op}/{method}",
                                   **_tol(dtype))


@pytest.mark.parametrize("op", dispatch.ops())
def test_every_engine_matches_oracle_under_jit(op, fresh_plan_registry):
    spec = dispatch.op_spec(op)
    x, kw = _op_inputs(op)
    want = np.asarray(spec.reference(x, **kw), dtype=np.float64)
    for method in spec.engine_names() + ("auto",):
        eng = spec.engine(method)
        if eng is not None and \
                dispatch._policy_reason(eng, None) is not None:
            with pytest.raises(ValueError, match="policy|accum|pair"):
                jax.jit(lambda v, m=method: dispatch.dispatch(
                    op, v, method=m, **kw))(x)
            continue
        fn = jax.jit(lambda v, m=method: dispatch.dispatch(
            op, v, method=m, **kw))
        got = np.asarray(fn(x))
        np.testing.assert_allclose(got, want,
                                   err_msg=f"jit {op}/{method}",
                                   **_tol(jnp.float32))


@pytest.mark.parametrize("engine", ["mma", "mma_chained", "vpu", "auto"])
def test_reduce_and_scan_under_vmap(engine, fresh_plan_registry):
    """The pure-JAX engines compose with vmap (the Pallas kernel owns
    only the un-vmapped single-device hot path)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 512)).astype(np.float32))
    got = np.asarray(jax.vmap(
        lambda v: ci.reduce_sum(v, method=engine))(x))
    np.testing.assert_allclose(got, np.sum(np.asarray(x), axis=1),
                               rtol=1e-5, atol=1e-3)
    got = np.asarray(jax.vmap(
        lambda v: ci.cumsum(v, method=engine))(x))
    np.testing.assert_allclose(got, np.cumsum(np.asarray(x), axis=1),
                               rtol=1e-4, atol=1e-3)


# ------------------------------------------------ axis-aware reductions


AXIS_CASES = [
    ((5, 7), 0), ((5, 7), 1), ((5, 7), -1), ((5, 7), (0, 1)),
    ((3, 4, 5), 1), ((3, 4, 5), (0, 2)), ((3, 4, 5), (1, 2)),
    ((2, 3, 4, 5), (0, 3)), ((2, 3, 4, 5), -2),
]


@pytest.mark.parametrize("shape,axis", AXIS_CASES)
@pytest.mark.parametrize("keepdims", [False, True])
def test_axis_aware_reduce_matches_vpu_baseline(shape, axis, keepdims,
                                                fresh_plan_registry):
    rng = np.random.default_rng(hash((shape, str(axis))) % 2**32)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    want = np.sum(np.asarray(x), axis=axis, keepdims=keepdims)
    for method in ("mma", "vpu", "auto"):
        got = np.asarray(ci.reduce_sum(x, axis=axis, keepdims=keepdims,
                                       method=method))
        assert got.shape == want.shape, (method, axis, keepdims)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4,
                                   err_msg=f"{method}/{axis}")
    got = np.asarray(ci.reduce_mean(x, axis=axis, keepdims=keepdims))
    np.testing.assert_allclose(
        got, np.mean(np.asarray(x), axis=axis, keepdims=keepdims),
        rtol=1e-5, atol=1e-5)
    got = np.asarray(ci.squared_sum(x, axis=axis, keepdims=keepdims))
    np.testing.assert_allclose(
        got, np.sum(np.asarray(x) ** 2, axis=axis, keepdims=keepdims),
        rtol=1e-4, atol=1e-4)


def test_axis_aware_reduce_bf16_contract(fresh_plan_registry):
    """bf16 multiplicands, f32 accumulators: the batched forms obey the
    same precision contract as the flat reduction."""
    rng = np.random.default_rng(11)
    x32 = rng.normal(size=(16, 384)).astype(np.float32)
    x = jnp.asarray(x32).astype(jnp.bfloat16)
    want = np.sum(np.asarray(x).astype(np.float32), axis=-1)
    got = np.asarray(ci.reduce_sum(x, axis=-1, method="mma"))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-1)


def test_axis_aware_under_jit_and_grad(fresh_plan_registry):
    x = jnp.asarray(np.random.default_rng(4)
                    .normal(size=(8, 64)).astype(np.float32))
    f = jax.jit(lambda v: ci.reduce_sum(v, axis=-1, method="auto"))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.sum(np.asarray(x), -1),
                               rtol=1e-5, atol=1e-4)
    g = jax.grad(lambda v: ci.reduce_sum(v * v, axis=0,
                                         method="mma").sum())(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x),
                               rtol=1e-5, atol=1e-4)


def test_duplicate_axes_raise():
    with pytest.raises(ValueError):
        ci.reduce_sum(jnp.ones((3, 4)), axis=(0, 0))


def test_out_of_range_axes_raise_not_wrap():
    """An off-by-one axis must error (jnp.sum semantics), never be
    silently wrapped modulo ndim onto the wrong axis."""
    x = jnp.ones((2, 3))
    for bad in (2, -3, (0, 2)):
        with pytest.raises(ValueError, match="out of bounds"):
            ci.reduce_sum(x, axis=bad)
        with pytest.raises(ValueError):
            ci.squared_sum(x, axis=bad)


def test_empty_axis_tuple_reduces_nothing():
    x = jnp.asarray(np.arange(6.0, dtype=np.float32).reshape(2, 3))
    got = ci.reduce_sum(x, axis=())
    assert got.shape == x.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(x))
    np.testing.assert_allclose(np.asarray(ci.squared_sum(x, axis=())),
                               np.asarray(x) ** 2)
    np.testing.assert_allclose(np.asarray(ci.reduce_mean(x, axis=())),
                               np.asarray(x))


def test_supported_method_probe():
    x2d = jnp.ones((4, 8))
    assert dispatch.supported_method("reduce_sum", x2d, "mma",
                                     axis=(1,))
    assert not dispatch.supported_method("reduce_sum", x2d, "pallas",
                                         axis=(1,))
    assert not dispatch.supported_method("reduce_sum", x2d, "nope")
    assert dispatch.supported_method("reduce_sum", x2d, "auto",
                                     axis=(1,))
    # resolve_method: identity for legal spellings, fallback otherwise
    assert dispatch.resolve_method("reduce_sum", x2d, "mma",
                                   axis=(1,)) == "mma"
    assert dispatch.resolve_method("reduce_sum", x2d, "pallas",
                                   fallback="vpu", axis=(1,)) == "vpu"
    assert dispatch.resolve_method("expert_counts", x2d, "nope",
                                   fallback="mma") == "mma"


def test_chain_auto_spelling_on_hooks(fresh_plan_registry):
    """chain='auto' resolves the engine-restricted tuned geometry from
    the plan registry on every hook (the pre-registry tc_reduce /
    mma_reduce 'auto' spelling, preserved through dispatch)."""
    x = jnp.asarray(np.random.default_rng(17)
                    .normal(size=40_000).astype(np.float32))
    want = float(np.sum(np.asarray(x), dtype=np.float64))
    for eng in ("mma_chained", "pallas"):
        got = float(ci.reduce_sum(x, method=eng, chain="auto"))
        assert abs(got - want) <= 1e-2, eng
    got = np.asarray(ci.cumsum(x[:3_000], method="mma", chain="auto"))
    np.testing.assert_allclose(
        got, np.cumsum(np.asarray(x[:3_000])), rtol=1e-4, atol=1e-2)
    # the engine-restricted keys were tuned (and run that engine)
    keys = dict(autotune.default_registry().items())
    assert any(k.endswith("|pallas") for k in keys)
    assert any(k.endswith("|mma_chained") for k in keys)


def test_rmsnorm_ablation_engines_fall_back(fresh_plan_registry):
    """A model must stay trainable under every reduce_method ablation:
    the flatten-only engines cannot serve the per-row statistic, so
    the norm maps them to the classic baseline instead of raising."""
    from repro.models import layers as L
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.normal(size=(4, 16, 32)).astype(np.float32))
    params = {"scale": jnp.zeros((32,), jnp.float32)}
    want = np.asarray(L.rmsnorm(params, x, method="vpu"))
    for ablation in ("pallas", "mma_chained"):
        got = np.asarray(L.rmsnorm(params, x, method=ablation))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # ... and the mma fast path still matches within f32 rounding
    np.testing.assert_allclose(
        np.asarray(L.rmsnorm(params, x, method="mma")), want,
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ablation", ["mma_chained", "pallas"])
def test_moe_aux_loss_survives_ablation_engines(ablation,
                                                fresh_plan_registry):
    """moe._aux_loss maps flatten-only reduce_method spellings onto the
    MMA row reduction (what they always ran) instead of crashing the
    forward pass — while the raw expert_counts hook stays strict."""
    import types
    from repro.models import moe
    rng = np.random.default_rng(31)
    probs = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32)), -1)
    ids = jnp.argsort(-probs, axis=-1)[:, :2]
    cfg = types.SimpleNamespace(
        moe=types.SimpleNamespace(num_experts=8),
        reduce_method=ablation)
    got = float(moe._aux_loss(cfg, probs, ids))
    cfg.reduce_method = "mma"
    np.testing.assert_allclose(
        got, float(moe._aux_loss(cfg, probs, ids)), rtol=1e-6)


@pytest.mark.parametrize("ablation", ["mma_chained", "pallas"])
def test_running_stats_survive_ablation_engines(ablation):
    """RunningStats keeps collecting per-sequence fill under the
    flatten-only engines (row statistic falls back to the baseline)."""
    from repro.data.pipeline import RunningStats
    stats = RunningStats(method=ablation)
    mask = np.ones((4, 16), np.float32)
    mask[1, 8:] = 0.0
    assert stats.update({"mask": mask}) == 56.0
    s = stats.summary()
    assert s["min_seq_tokens"] == 8.0 and s["max_seq_tokens"] == 16.0


# ---------------------------------------------------- capability layer


def test_illegal_engines_raise_structurally():
    """The registry's capability predicates make misrouting an error:
    no hook may silently fall through to a different engine."""
    onehot = jnp.ones((32, 8), jnp.float32)
    for bad in ("pallas", "mma_chained", "tpu", ""):
        with pytest.raises(ValueError):
            ci.expert_counts(onehot, method=bad)
    # flatten-only engines reject axis-subset (batched) reductions
    for bad in ("pallas", "mma_chained"):
        with pytest.raises(ValueError):
            ci.reduce_sum(jnp.ones((4, 8)), axis=1, method=bad)
    # the Pallas scan owns only the flattened layout
    with pytest.raises(ValueError):
        ci.cumsum(jnp.ones((4, 8)), axis=-1, method="pallas")
    # unknown spellings name the accepted set per-op
    with pytest.raises(ValueError, match="accepted"):
        ci.segment_sum(jnp.ones(8), jnp.zeros(8, jnp.int32), 2,
                       method="nope")
    with pytest.raises(ValueError):
        dispatch.dispatch("not_an_op", jnp.ones(8))


def test_multi_device_predicates_restrict_legal_set():
    """Under a >1-device mesh only the distribution-safe engines stay
    legal (checked against a synthetic context — CI hosts are
    single-device)."""
    spec = dispatch.op_spec("reduce_sum")
    ctx = dispatch.DispatchContext(op="reduce_sum", shape=(1024,),
                                   dtype="float32", multi_device=True)
    assert dispatch.legal_engines(spec, ctx) == ("mma", "vpu")
    scan_spec = dispatch.op_spec("scan")
    ctx = dispatch.DispatchContext(op="scan", shape=(1024,),
                                   dtype="float32", multi_device=True,
                                   scan_axis=0)
    assert dispatch.legal_engines(scan_spec, ctx) == \
        ("mma_chained", "vpu")
    # single-device, flat: every engine is legal -> unrestricted key
    ctx = dispatch.DispatchContext(op="scan", shape=(1024,),
                                   dtype="float32", multi_device=False,
                                   scan_axis=0)
    assert dispatch.legal_engines(scan_spec, ctx) == \
        scan_spec.engine_names()


def test_attention_capability_predicates(fresh_plan_registry):
    """The attention engines' predicates gate on problem structure —
    misrouting a decode (dynamic kv_len) problem onto the dense-prefill
    engine, or an oversized head dim onto the fused kernel, is a
    ``ValueError`` naming the reason, never a silent wrong answer."""
    qg, kw = _op_inputs("attention")
    spec = dispatch.op_spec("attention")
    kv_len = jnp.asarray([5, 9], jnp.int32)   # dynamic per-row count
    kw_dec = dict(kw, kv_len=kv_len)
    with pytest.raises(ValueError, match="kv_len"):
        dispatch.dispatch("attention", qg, method="unfused_mma",
                          **kw_dec)
    assert not dispatch.supported_method("attention", qg,
                                         "unfused_mma", **kw_dec)
    assert dispatch.resolve_method("attention", qg, "unfused_mma",
                                   fallback="vpu", **kw_dec) == "vpu"
    # a *static* full-length kv_len is dense prefill: still legal
    assert dispatch.supported_method(
        "attention", qg, "unfused_mma",
        **dict(kw, kv_len=int(kw["k"].shape[1])))
    # fused kernel refuses head dims past its VMEM lane tiling
    rng = np.random.default_rng(2)
    qh = jnp.asarray(rng.normal(size=(1, 8, 1, 1, 600))
                     .astype(np.float32))
    kw_hd = {"k": jnp.asarray(rng.normal(size=(1, 8, 1, 600))
                              .astype(np.float32)),
             "v": jnp.asarray(rng.normal(size=(1, 8, 1, 16))
                              .astype(np.float32)),
             "qpos": jnp.arange(8, dtype=jnp.int32), "causal": True}
    with pytest.raises(ValueError, match="head dim"):
        dispatch.dispatch("attention", qh, method="fused_pallas",
                          **kw_hd)
    # the auto path prunes to legal engines *before* planning: decode
    # still matches the oracle and the plan key records the restriction
    got = np.asarray(dispatch.dispatch("attention", qg, method="auto",
                                       **kw_dec))
    want = np.asarray(spec.reference(qg, **kw_dec), dtype=np.float64)
    np.testing.assert_allclose(got, want, **_tol(jnp.float32))
    keys = [k for k, _ in autotune.default_registry().items()]
    assert any(k.startswith("attention") and
               k.endswith("|fused_pallas+vpu") for k in keys), keys


def test_norm_matmul_capability_predicates(fresh_plan_registry):
    """The norm_matmul engines' predicates gate on d_model: an
    oversized model dim refuses the fused kernel by name, the
    stay-trainable resolver maps it to the unfused two-op path, and
    the auto plan key records the restricted engine set."""
    spec = dispatch.op_spec("norm_matmul")
    rng = np.random.default_rng(5)
    xb = jnp.asarray(rng.normal(size=(4, 1024)).astype(np.float32))
    kw_b = {"w": jnp.asarray(rng.normal(size=(1024, 16))
                             .astype(np.float32) / 32.0),
            "scale": jnp.zeros((1024,), jnp.float32)}
    with pytest.raises(ValueError, match="d_model"):
        dispatch.dispatch("norm_matmul", xb, method="fused_pallas",
                          **kw_b)
    assert not dispatch.supported_method("norm_matmul", xb,
                                         "fused_pallas", **kw_b)
    assert dispatch.resolve_method(
        "norm_matmul", xb, "fused_pallas", fallback="unfused_mma",
        **kw_b) == "unfused_mma"
    got = np.asarray(dispatch.dispatch("norm_matmul", xb,
                                       method="auto", **kw_b))
    want = np.asarray(spec.reference(xb, **kw_b), dtype=np.float64)
    np.testing.assert_allclose(got, want, **_tol(jnp.float32))
    keys = [k for k, _ in autotune.default_registry().items()]
    assert any(k.startswith("norm_matmul") and
               k.endswith("|unfused_mma+vpu") for k in keys), keys
    # layers.rmsnorm's fused spellings resolve through the registry's
    # norm-only (w=None) form — the legacy standalone rmsnorm kernel
    # is no longer reachable only via a dispatch() bypass.
    from repro.models import layers as L
    params = {"scale": jnp.asarray(0.1 * rng.normal(size=32),
                                   jnp.float32)}
    xs = jnp.asarray(rng.normal(size=(4, 8, 32)).astype(np.float32))
    want = np.asarray(L.rmsnorm(params, xs, method="vpu"))
    for spelling in ("fused_pallas", "unfused_mma"):
        got = np.asarray(L.rmsnorm(params, xs, method=spelling))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=spelling)


def test_norm_matmul_fused_over_several_tiles(fresh_plan_registry):
    """The fused kernel over two row tiles, three k-blocks and three
    output tiles (dout 1152 = 3 x 384 lanes) matches the reference:
    the row statistic the first output tile computes serves the rest."""
    spec = dispatch.op_spec("norm_matmul")
    rng = np.random.default_rng(8)

    def t(*shape, s=1.0):
        return jnp.asarray(s * rng.normal(size=shape).astype(np.float32))

    x = t(200, 300)
    kw = {"w": t(300, 1152, s=0.05), "scale": t(300, s=0.1),
          "w_gate": t(300, 1152, s=0.05), "bias": t(1152), "act": "gelu"}
    got = np.asarray(dispatch.dispatch("norm_matmul", x,
                                       method="fused_pallas", **kw))
    want = np.asarray(spec.reference(x, **kw), dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_norm_matmul_auto_error_budget(fresh_plan_registry):
    """method='auto' arbitrates fused-vs-unfused under the policy's
    error budget: a 0.5% budget admits the bf16-multiplicand fused
    kernel (modelled ~0.2%) and picks it as the cheaper plan, while a
    punishing 1e-4% budget nothing passes falls back to the most
    accurate engine — the full-f32 unfused two-op path (its registered
    engine_bits), never the fused kernel."""
    from repro.core.precision import MmaPolicy
    x, kw = _op_inputs("norm_matmul")
    spec = dispatch.op_spec("norm_matmul")
    want = np.asarray(spec.reference(x, **kw), dtype=np.float64)
    got = np.asarray(dispatch.dispatch(
        "norm_matmul", x, method="auto",
        precision=MmaPolicy(error_budget_pct=0.5), **kw))
    np.testing.assert_allclose(got, want, **_tol(jnp.float32))
    got = np.asarray(dispatch.dispatch(
        "norm_matmul", x, method="auto",
        precision=MmaPolicy(error_budget_pct=1e-4), **kw))
    np.testing.assert_allclose(got, want, **_tol(jnp.float32))
    plans = dict(autotune.default_registry().items())
    loose = {plans[k].method for k in plans
             if k.startswith("norm_matmul") and k.endswith("b0.5")}
    tight = {plans[k].method for k in plans
             if k.startswith("norm_matmul") and k.endswith("b0.0001")}
    assert loose == {"fused_pallas"}, plans
    assert tight == {"unfused_mma"}, plans


def test_candidate_plans_follow_registry():
    """The autotuner's sweep space is the registry's engine space —
    minus the policy-gated engines (the dd family) on an unrestricted
    no-policy sweep, where the default f32-scalar contract holds."""
    for op in dispatch.ops():
        spec = dispatch.op_spec(op)
        methods = {p.method for p in
                   autotune.candidate_plans(1 << 16, jnp.float32, op=op)}
        sweepable = {e.name for e in spec.engines
                     if dispatch._policy_reason(e, None) is None}
        assert methods == sweepable, op
        # an explicit engine restriction still enumerates gated engines
        for eng in spec.engines:
            assert {p.method for p in autotune.candidate_plans(
                1 << 16, jnp.float32, op=op,
                engine=(eng.name,))} == {eng.name}, (op, eng.name)
    # expert_counts is row-wise: exactly the contraction + baseline
    assert {p.method for p in autotune.candidate_plans(
        1 << 16, jnp.float32, op="expert_counts")} == {"mma", "vpu"}


def test_single_executor_runs_every_family(fresh_plan_registry):
    """autotune exposes exactly one plan executor, and it serves all
    three op families through the registry runners."""
    assert not hasattr(autotune, "execute_scan_plan")
    assert not hasattr(autotune, "execute_segment_plan")
    x = jnp.asarray(np.random.default_rng(8)
                    .normal(size=1_000).astype(np.float32))
    plan = autotune.ReductionPlan(method="vpu")
    np.testing.assert_allclose(
        float(autotune.execute_plan(x, plan)),
        float(jnp.sum(x)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(autotune.execute_plan(x, plan, op="scan")),
        np.cumsum(np.asarray(x)), rtol=1e-5, atol=1e-4)
    ids = jnp.asarray(np.arange(1_000, dtype=np.int32) % 5)
    np.testing.assert_allclose(
        np.asarray(autotune.execute_plan(
            x, plan, op="segment_sum", segment_ids=ids,
            num_segments=5)),
        np.asarray(dispatch.op_spec("segment_sum").reference(
            x, segment_ids=ids, num_segments=5)), rtol=1e-5)
    # a plan whose engine the op does not declare is refused
    with pytest.raises(ValueError):
        autotune.execute_plan(x, autotune.ReductionPlan(
            method="mma_chained"), op="expert_counts")


def test_auto_path_restricts_to_legal_engines(fresh_plan_registry):
    """A batched (axis-subset) auto reduction may only ever execute a
    batched-capable engine, whatever the sweep would prefer."""
    x = jnp.asarray(np.random.default_rng(9)
                    .normal(size=(32, 2048)).astype(np.float32))
    got = ci.reduce_sum(x, axis=-1, method="auto")
    np.testing.assert_allclose(np.asarray(got),
                               np.sum(np.asarray(x), -1),
                               rtol=1e-5, atol=1e-3)
    keys = [k for k, _ in autotune.default_registry().items()]
    restricted = [k for k in keys if k.startswith("reduce_sum")
                  and k.endswith("|mma+vpu")]
    assert restricted, keys


# ------------------------------- the eager mma engine: one program


MMA_HOOKS = {"reduce_sum": ci.reduce_sum, "squared_sum": ci.squared_sum}
MMA_INPUTS = {                     # name -> (shape, axis)
    "1d": ((4_111,), None),
    "3d_all": ((5, 9, 13), None),
    "3d_subset": ((5, 9, 13), (0, 2)),
}
_MMA_CASES = [(op, case) for op in MMA_HOOKS for case in MMA_INPUTS]


def _mma_input(case):
    shape, axis = MMA_INPUTS[case]
    rng = np.random.default_rng(sum(shape))
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)), axis


def _mma_program(op, x, axis):
    """The program the ``mma`` runner of ``op`` compiles for ``x``."""
    from repro.core import reduction as R
    return R._contraction.lower(x, axes=axis, square=op == "squared_sum",
                                contract=R.tc_contract)


@pytest.fixture()
def compiled_programs():
    """Names of the programs JAX compiles while the test runs."""
    from jax import monitoring
    from jax._src import dispatch as jax_dispatch
    seen = []

    def on(event, secs, **kw):
        if event == jax_dispatch.BACKEND_COMPILE_EVENT:
            seen.append(kw.get("fun_name", "?"))

    monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on)


@pytest.mark.parametrize("op,case", _MMA_CASES)
def test_eager_mma_is_one_program(op, case, compiled_programs):
    """An eager ``mma`` call compiles one program, no ``ones_like``
    beside it, and runs that program again on the next call."""
    x, axis = _mma_input(case)
    jax.clear_caches()
    dispatch.dispatch(op, x, method="mma", axis=axis).block_until_ready()
    assert compiled_programs == ["jit(_contraction)"], compiled_programs
    dispatch.dispatch(op, x, method="mma", axis=axis).block_until_ready()
    assert compiled_programs == ["jit(_contraction)"], compiled_programs


@pytest.mark.parametrize("op,case", _MMA_CASES)
def test_eager_mma_program_reads_only_x_in_f32(op, case):
    """The program's only parameter is ``x`` (no ones operand), it has
    no bf16 anywhere, and its contraction returns f32."""
    x, axis = _mma_input(case)
    low = _mma_program(op, x, axis)
    text = low.as_text()
    main = next(l for l in text.splitlines() if "func.func public @main" in l)
    assert main.count("%arg") == 1, main
    assert "bf16" not in text
    dots = [l for l in text.splitlines() if "stablehlo.dot_general" in l]
    assert len(dots) == 1, dots
    assert dots[0].rstrip().endswith("f32>"), dots[0]
    hlo = low.compile().as_text()
    entry = hlo.split("ENTRY")[1]
    assert entry.count("parameter(") == 1, entry
    assert "bf16" not in hlo


@pytest.mark.parametrize("op,case", _MMA_CASES)
def test_eager_mma_matches_float64_and_vpu(op, case):
    x, axis = _mma_input(case)
    x64 = np.asarray(x, np.float64)
    want = np.sum(x64 * x64 if op == "squared_sum" else x64, axis=axis)
    got = dispatch.dispatch(op, x, method="mma", axis=axis)
    assert got.dtype == jnp.float32 and got.shape == np.shape(want)
    tol = dict(rtol=1e-5, atol=1e-5 * np.sqrt(x.size))
    np.testing.assert_allclose(np.asarray(got, np.float64), want, **tol)
    vpu = dispatch.dispatch(op, x, method="vpu", axis=axis)
    np.testing.assert_allclose(np.asarray(got), np.asarray(vpu), **tol)


@pytest.mark.parametrize("op", MMA_HOOKS)
def test_mma_under_outer_jit_is_one_contraction_of_x(op):
    """Inside a caller's jit the runner's program is inlined: one
    contraction, the ones (if any) made from a scalar in the program,
    and ``x`` the only parameter."""
    x, _ = _mma_input("3d_all")
    low = jax.jit(lambda v: MMA_HOOKS[op](v, method="mma")).lower(x)
    text = low.as_text()
    assert text.count("stablehlo.dot_general") == 1, text
    main = next(l for l in text.splitlines() if "func.func public @main" in l)
    assert main.count("%arg") == 1, main
    ones = [l for l in text.splitlines() if "dense<1.000000e+00>" in l]
    assert all(l.rstrip().endswith("tensor<f32>") for l in ones), ones
    assert low.compile().as_text().split("ENTRY")[1].count(
        "parameter(") == 1


_SHARDED_PROG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import integration as ci, reduction as R

mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
xh = np.random.default_rng(0).normal(size=4096).astype(np.float32)
x = jax.device_put(jnp.asarray(xh), NamedSharding(mesh, P("d")))
out = {}
for op, sq in (("reduce_sum", False), ("squared_sum", True)):
    got = float(getattr(ci, op)(x, method="mma"))
    hlo = R._contraction.lower(x, axes=None, square=sq,
                               contract=R.tc_contract).compile().as_text()
    x64 = xh.astype(np.float64)
    out[op] = {"got": got, "want": float(np.sum(x64 * x64 if sq else x64)),
               "all_reduce": hlo.count(" all-reduce("),
               "local": "f32[1024]" in hlo, "global": "f32[4096]" in hlo}
print("RESULT" + json.dumps(out))
"""


def test_eager_mma_of_a_sharded_input_is_local_plus_one_all_reduce():
    """On a 4-device mesh the eager program contracts each shard where
    it lies and combines the partial sums with one all-reduce."""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", _SHARDED_PROG],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][0]
    for op, r in json.loads(line[len("RESULT"):]).items():
        assert r["all_reduce"] == 1, (op, r)
        assert r["local"] and not r["global"], (op, r)
        np.testing.assert_allclose(r["got"], r["want"], rtol=1e-5,
                                   err_msg=op)

"""Numerical-precision reproduction of the paper's §5.4/§6 claims,
adapted to TPU bf16 semantics (docs/design-notes.md §8):

  * single-pass keeps f32 partials -> error stays small on both input
    distributions (paper: <1% normal, <0.001% uniform);
  * the recurrence variant with low-precision partials degrades on
    uniform inputs (paper: FP16 *overflows*; bf16 has f32 range, so the
    failure becomes measurable precision loss instead).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, dispatch, tc_reduce
from repro.core import integration as ci
from repro.core.precision import (EXACT_OFFSETS, MmaPolicy, as_policy,
                                  compensated_sum, error_sweep,
                                  fp64_oracle, normal_input,
                                  percent_error, split_f32_words,
                                  uniform_input)


def _reduce_bf16(variant, keep_f32=True):
    def f(x):
        xb = jnp.asarray(x.astype(np.float32)).astype(jnp.bfloat16)
        return float(tc_reduce(xb, variant=variant,
                               keep_f32_partials=keep_f32))
    return f


def test_single_pass_normal_under_1pct():
    rows = error_sweep(_reduce_bf16("single_pass"), [10**5, 10**6],
                       dist="normal")
    for n, err in rows:
        assert err < 1.0, (n, err)   # paper: <1% for n >= 1e7 (normal)


def test_single_pass_uniform_small_error():
    rows = error_sweep(_reduce_bf16("single_pass"), [10**5, 10**6],
                       dist="uniform")
    for n, err in rows:
        assert err < 0.05, (n, err)


def test_recurrence_low_precision_partials_degrade():
    """Paper Fig. 7: the recurrence variant fails on uniform inputs when
    partials re-enter the multiply precision."""
    n = 10**6
    x = uniform_input(n, seed=3)
    good = percent_error(_reduce_bf16("single_pass")(x), x)
    bad = percent_error(_reduce_bf16("recurrence", keep_f32=False)(x), x)
    assert bad > 10 * good, (bad, good)
    # bf16's f32-range exponent means no overflow (unlike FP16/CUB-half):
    assert np.isfinite(bad)


def test_f32_partials_rescue_recurrence():
    n = 10**6
    x = uniform_input(n, seed=4)
    err = percent_error(_reduce_bf16("recurrence", keep_f32=True)(x), x)
    assert err < 0.05


def test_fp32_input_is_exact_enough():
    x = normal_input(10**6, seed=5).astype(np.float32)
    err = percent_error(float(tc_reduce(jnp.asarray(x))), x)
    assert err < 1e-3


def test_oracle_self_consistency():
    x = np.ones(1000)
    assert fp64_oracle(x) == 1000.0
    assert percent_error(1000.0, x) == 0.0


# ================== the compensated split-bf16 family (mma_ec) =======


def _pct(got, x64):
    return percent_error(float(got), x64)


@pytest.mark.parametrize("dist", ["uniform", "normal"])
@pytest.mark.parametrize("n", [1 << 16, 1 << 20, 1 << 24])
def test_mma_ec_paper_harness(dist, n):
    """Paper-harness cases for mma_ec: percent error vs the fp64
    oracle stays at (sub-)f32 levels on both input classes up to
    2^24 — the compensated family's accuracy contract."""
    gen = uniform_input if dist == "uniform" else normal_input
    x32 = gen(n, seed=7).astype(np.float32)
    xj = jnp.asarray(x32)
    x64 = x32.astype(np.float64)
    pol3 = MmaPolicy(split_words=3)
    err3 = _pct(dispatch.dispatch("reduce_sum", xj, method="mma_ec",
                                  precision=pol3), x64)
    assert err3 < 1e-3, (dist, n, err3)
    if dist == "uniform":     # the paper's hard case: near-exact
        err2 = _pct(dispatch.dispatch("reduce_sum", xj,
                                      method="mma_ec"), x64)
        assert err2 < 1e-4, (n, err2)


def test_mma_ec_beats_vpu_on_uniform_2_20():
    """The acceptance bar: at n=2^20 on uniform [0,1] f32 inputs the
    compensated engine's percent error is no worse than the classic
    jnp.sum baseline's, and at (or under) the correctly-rounded floor
    (a pairwise baseline can reach that floor too, so it ties)."""
    n = 1 << 20
    x32 = uniform_input(n, seed=17).astype(np.float32)
    xj = jnp.asarray(x32)
    x64 = x32.astype(np.float64)
    err_vpu = _pct(dispatch.dispatch("reduce_sum", xj, method="vpu"),
                   x64)
    err_ec = _pct(dispatch.dispatch("reduce_sum", xj, method="mma_ec"),
                  x64)
    assert err_ec <= err_vpu, (err_ec, err_vpu)
    assert err_ec < 1e-4, err_ec
    # the correctly-rounded f32 reference: ec sits at (or under) the
    # rounding floor of the result itself
    floor = _pct(np.float32(np.sum(x64)), x64)
    assert err_ec <= max(floor * 4.0, 1e-5)


def test_mma_ec_within_2x_mma_model_cost():
    """The runtime side of the acceptance bar, in the deterministic
    cost model (the TPU-faithful score — XLA-CPU emulates bf16 dots at
    near-f32 price, so wall clock is reported in the bench table
    instead): the default 2-word compensated engine prices within 2x
    the plain contraction."""
    n = 1 << 20
    mma = autotune.model_cost(
        autotune.ReductionPlan(method="mma"), n, jnp.float32)
    ec2 = autotune.model_cost(
        autotune.ReductionPlan(method="mma_ec", chain=2,
                               split_words=2), n, jnp.float32)
    assert ec2 <= 2.0 * mma, (ec2, mma)


def test_mma_ec_selectable_for_all_three_ops(fresh_plan_registry):
    """dispatch(op, x, method='mma_ec') serves reduce_sum /
    squared_sum / scan (the engine-family acceptance surface)."""
    rng = np.random.default_rng(3)
    x32 = rng.normal(size=5_000).astype(np.float32)
    xj = jnp.asarray(x32)
    x64 = x32.astype(np.float64)
    # default 2-word split: ~16-bit multiplicands, so a cancelling
    # normal sum carries ~|x|_1 * 2^-17 of representation residual
    got = float(dispatch.dispatch("reduce_sum", xj, method="mma_ec"))
    np.testing.assert_allclose(got, x64.sum(), rtol=1e-4, atol=1e-3)
    got = float(dispatch.dispatch("squared_sum", xj, method="mma_ec"))
    np.testing.assert_allclose(got, (x64 ** 2).sum(), rtol=1e-5)
    got = np.asarray(dispatch.dispatch("scan", xj, method="mma_ec"))
    np.testing.assert_allclose(got, np.cumsum(x64), rtol=1e-5,
                               atol=1e-3)
    # batched scan keeps its leading axis
    xb = jnp.asarray(rng.normal(size=(4, 640)).astype(np.float32))
    got = np.asarray(ci.cumsum(xb, method="mma_ec"))
    np.testing.assert_allclose(got, np.cumsum(np.asarray(xb), -1),
                               rtol=1e-5, atol=1e-3)


def test_pallas_ec_kernel_matches_compensated_ref():
    from repro.kernels import mma_ec_reduce, mma_ec_squared_sum
    from repro.kernels.ref import ec_reduce_ref
    rng = np.random.default_rng(11)
    x32 = rng.uniform(0, 1, 70_000).astype(np.float32)
    xj = jnp.asarray(x32)
    x64 = x32.astype(np.float64)
    for words in (2, 3):
        got = float(mma_ec_reduce(xj, split_words=words, chain=2,
                                  interpret=True))
        want = float(ec_reduce_ref(xj, split_words=words))
        np.testing.assert_allclose(got, want, rtol=1e-7)
        assert percent_error(got, x64) < 1e-4
    got = float(mma_ec_squared_sum(xj, split_words=2, chain=2,
                                   interpret=True))
    assert percent_error(got, x64 ** 2) < 1e-4


# ======================== split-bf16 exactness ======================


def test_three_word_split_reconstructs_within_1_ulp():
    """3 x 8 significand bits cover f32's 24: hi+mid+lo recombines to
    the original f32 value within 1 ulp (exactly, for normals) —
    across 40 binades of magnitude."""
    rng = np.random.default_rng(0)
    x32 = (rng.normal(size=8_192) *
           np.exp2(rng.integers(-20, 20, 8_192))).astype(np.float32)
    xj = jnp.asarray(x32)
    parts = split_f32_words(xj, 3)
    recon = np.asarray(sum(p.astype(jnp.float32) for p in parts))
    ulp = np.spacing(np.abs(x32))
    assert np.max(np.abs(recon - x32) / ulp) <= 1.0


def test_two_word_split_residual_bound():
    """hi+lo keeps ~16 of f32's 24 significand bits: relative residual
    bounded by 2^-15 (two round-to-nearest halvings of 8 bits)."""
    rng = np.random.default_rng(1)
    x32 = rng.normal(size=8_192).astype(np.float32)
    xj = jnp.asarray(x32)
    parts = split_f32_words(xj, 2)
    recon = np.asarray(sum(p.astype(jnp.float32) for p in parts))
    rel = np.abs(recon - x32) / np.maximum(np.abs(x32), 1e-30)
    assert np.max(rel) <= 2.0 ** -15


def test_compensated_sum_survives_adversarial_cancellation():
    """The TwoSum tree stays within a couple of ulps of the exact sum
    under an adversarial magnitude spread (condition number ~1e8,
    where a plain f32 sum loses every significant digit) — the
    first-order errors are captured exactly; only the second-order
    fold of the error terms themselves can round."""
    vals = np.array([1e8, 1.0, -1e8, 1.0, 0.25, -0.25, 3.5e-4] * 9,
                    dtype=np.float32)
    want64 = vals.astype(np.float64).sum()
    got = float(compensated_sum(jnp.asarray(vals)))
    assert abs(got - want64) <= 2 * np.spacing(np.float32(want64)), \
        (got, want64)
    plain = float(jnp.sum(jnp.asarray(vals)))
    assert abs(got - want64) < abs(plain - want64)


# =================== policy: plan keys and selection =================


def test_policy_signature_grammar():
    assert MmaPolicy().signature() == "any.float32"
    assert MmaPolicy(split_words=2).signature() == "any.float32.w2"
    sig = MmaPolicy(input_dtype=jnp.bfloat16, split_words=3,
                    error_budget_pct=1e-4,
                    mma_precision="highest").signature()
    assert sig == "bfloat16.float32.w3.b0.0001.phighest"


def test_plan_key_precision_suffix_composes():
    pol = MmaPolicy(split_words=2)
    plain = autotune.plan_key("reduce_sum", 2**20, jnp.float32)
    prec = autotune.plan_key("reduce_sum", 2**20, jnp.float32,
                             policy=pol)
    assert prec == plain + "|prec:any.float32.w2"
    # fixed composition order: [engine][prec][mesh]
    full = autotune.plan_key("reduce_sum", 2**20, jnp.float32,
                             engine=("mma_ec",), policy=pol,
                             mesh="data4.model2")
    assert full.endswith(
        "|mma_ec|prec:any.float32.w2|mesh:data4.model2")


def test_policy_round_trips_through_dispatch_plan_keys(
        fresh_plan_registry):
    """An auto dispatch under a policy tunes, caches, and re-resolves
    under the precision-suffixed key — and the registry JSON
    round-trips it."""
    autotune.reset_default_registry()
    pol = MmaPolicy(split_words=2)
    x = jnp.asarray(np.random.default_rng(2)
                    .uniform(0, 1, 4_096).astype(np.float32))
    ci.reduce_sum(x, method="auto", precision=pol)
    reg = autotune.default_registry()
    keys = [k for k, _ in reg.items()]
    tagged = [k for k in keys if "|prec:" + pol.signature() in k]
    assert tagged, keys
    plan = reg.get(tagged[0])
    assert plan.split_words == 2
    before = len(reg)
    ci.reduce_sum(x, method="auto", precision=pol)   # cache hit
    assert len(reg) == before
    # JSON round-trip preserves precision-keyed entries exactly
    reloaded = autotune.PlanRegistry.from_json(reg.to_json())
    assert reloaded.get(tagged[0]) == plan
    autotune.reset_default_registry()


def test_budget_constrained_auto_resolves_mma_ec(fresh_plan_registry):
    """With a tight error budget, plain mma (bf16-truncated
    multiplicands in the model) and the vpu baseline both exceed the
    ceiling, so method='auto' provably resolves the compensated
    engine — asserted via plan-key inspection."""
    autotune.reset_default_registry()
    n = 1 << 20
    pol = MmaPolicy(error_budget_pct=1e-4)
    # the premise, in the model's own terms:
    assert autotune.model_percent_error(
        autotune.ReductionPlan(method="mma"), n, jnp.float32) > 1e-4
    assert autotune.model_percent_error(
        autotune.ReductionPlan(method="vpu"), n, jnp.float32) > 1e-4
    assert autotune.model_percent_error(
        autotune.ReductionPlan(method="mma_ec", split_words=3),
        n, jnp.float32) <= 1e-4
    x = jnp.asarray(uniform_input(n, seed=5).astype(np.float32))
    ci.reduce_sum(x, method="auto", precision=pol)
    reg = autotune.default_registry()
    key = autotune.plan_key("reduce_sum", n, jnp.float32, policy=pol)
    plan = reg.get(key)
    assert plan is not None, [k for k, _ in reg.items()]
    assert plan.method == "mma_ec", plan
    assert plan.split_words == 3
    assert plan.error_pct is not None and plan.error_pct <= 1e-4
    autotune.reset_default_registry()


def test_split_word_policy_is_a_capability_predicate():
    """A split-word policy is only legal on the mma_ec family: plain
    engines raise naming the reason, auto restricts to the family."""
    x = jnp.ones((4_096,), jnp.float32)
    pol = MmaPolicy(split_words=2)
    for bad in ("vpu", "mma", "mma_chained", "pallas"):
        with pytest.raises(ValueError, match="split_words"):
            ci.reduce_sum(x, method=bad, precision=pol)
    # accumulator contract: nothing serves f64 accumulation
    with pytest.raises(ValueError, match="accum_dtype"):
        ci.reduce_sum(x, method="vpu",
                      precision=MmaPolicy(accum_dtype=jnp.float64))
    spec = dispatch.op_spec("reduce_sum")
    ctx = dispatch.build_context("reduce_sum", x, policy=pol)
    assert dispatch.legal_engines(spec, ctx) == ("mma_ec", "pallas_ec")


def test_as_policy_back_compat_and_exact_offsets():
    """Hooks still accept a bare lax.Precision (wrapped into a
    policy), and the named EXACT_OFFSETS policy keeps integer prefix
    offsets exact through the triangular-MMA scan (the MoE path)."""
    pol = as_policy(jax.lax.Precision.HIGHEST)
    assert isinstance(pol, MmaPolicy)
    assert pol.lax_precision() == jax.lax.Precision.HIGHEST
    assert as_policy(pol) is pol and as_policy(None) is None
    counts = jnp.asarray(
        np.random.default_rng(4).integers(0, 4_000, 256), jnp.int32)
    got = ci.cumsum(counts, inclusive=False, method="mma", chain=1,
                    precision=EXACT_OFFSETS)
    want = np.cumsum(np.asarray(counts)) - np.asarray(counts)
    np.testing.assert_array_equal(np.round(np.asarray(got)), want)


def test_policy_input_cast_reaches_plain_engines():
    """input_dtype is the paper's low-precision-multiplicand ablation:
    a bf16 policy degrades the plain engine to bf16-input error, while
    the split family ignores the cast (it decomposes the f32 input
    itself)."""
    x32 = uniform_input(1 << 16, seed=9).astype(np.float32)
    xj = jnp.asarray(x32)
    x64 = x32.astype(np.float64)
    pol = MmaPolicy(input_dtype=jnp.bfloat16)
    err_cast = _pct(dispatch.dispatch("reduce_sum", xj, method="mma",
                                      precision=pol), x64)
    err_f32 = _pct(dispatch.dispatch("reduce_sum", xj, method="mma"),
                   x64)
    assert err_cast > 3 * max(err_f32, 1e-7), (err_cast, err_f32)
    err_ec = _pct(dispatch.dispatch("reduce_sum", xj, method="mma_ec",
                                    precision=pol), x64)
    assert err_ec < 1e-4, err_ec


def test_local_plan_auto_respects_split_policy(fresh_plan_registry):
    """The collectives' pre-shard_map plan resolver may only ever hand
    back a plan the policy's execute-time predicates will accept: auto
    resolves into the compensated family, and an explicit plain
    spelling raises at resolve time with the policy reason."""
    autotune.reset_default_registry()
    pol = MmaPolicy(split_words=2)
    plan = dispatch.local_plan("reduce_sum", 1 << 16, jnp.float32,
                               "auto", precision=pol)
    assert plan.method in ("mma_ec", "pallas_ec"), plan
    assert plan.split_words == 2
    with pytest.raises(ValueError, match="split_words"):
        dispatch.local_plan("reduce_sum", 1 << 16, jnp.float32,
                            "mma", precision=pol)
    autotune.reset_default_registry()


def test_resolve_method_never_hands_back_a_doomed_fallback():
    """A policy is never silently dropped: when neither the asked
    method nor the fallback can honour it (split words on a per-row
    statistic), resolve_method raises at the resolve point instead of
    returning a fallback that would crash inside dispatch."""
    x = jnp.ones((4, 256), jnp.float32)
    pol = MmaPolicy(split_words=2)
    with pytest.raises(ValueError, match="fallback"):
        dispatch.resolve_method("reduce_sum", x, "mma",
                                fallback="vpu", precision=pol,
                                axis=(1,))
    # without the impossible policy the ablation contract holds
    assert dispatch.resolve_method("reduce_sum", x, "pallas",
                                   fallback="vpu", axis=(1,)) == "vpu"
    # and rmsnorm surfaces the same clear error rather than a deep one
    from repro.models import layers as L
    params = {"scale": jnp.zeros((256,), jnp.float32)}
    with pytest.raises(ValueError, match="no engine"):
        L.rmsnorm(params, x, precision=pol)


def test_collectives_single_device_honour_policy(fresh_plan_registry):
    """tc_psum's no-mesh fallback threads the policy through the plain
    dispatch path (budget auto resolves the compensated engine)."""
    from repro.distributed.tc_collectives import tc_psum
    autotune.reset_default_registry()
    x = jnp.asarray(uniform_input(1 << 16, seed=6).astype(np.float32))
    pol = MmaPolicy(error_budget_pct=1e-4)
    got = float(tc_psum(x, precision=pol))
    np.testing.assert_allclose(got, float(np.asarray(x, np.float64)
                                          .sum()), rtol=1e-6)
    keys = [k for k, _ in autotune.default_registry().items()]
    assert any("|prec:" in k for k in keys), keys
    autotune.reset_default_registry()


# =============== double-double: the f64-equivalent tier ===============


def test_two_sum_is_bitwise_error_free():
    """Knuth TwoSum (branch-free, the dd carry primitive): s is
    EXACTLY fl(a+b) and s + e is EXACTLY a + b — bitwise, elementwise,
    across 12 decades of misaligned exponents (f64 holds the 48-bit
    exact sum of two f32s, so the check is equality, not closeness)."""
    from repro.core.precision import two_sum
    rng = np.random.default_rng(11)
    a32 = (rng.normal(size=4_096) *
           10.0 ** rng.uniform(-6, 6, 4_096)).astype(np.float32)
    b32 = (rng.normal(size=4_096) *
           10.0 ** rng.uniform(-6, 6, 4_096)).astype(np.float32)
    s, e = two_sum(jnp.asarray(a32), jnp.asarray(b32))
    s, e = np.asarray(s), np.asarray(e)
    np.testing.assert_array_equal(s, a32 + b32)          # s == fl(a+b)
    np.testing.assert_array_equal(                       # s + e exact
        s.astype(np.float64) + e.astype(np.float64),
        a32.astype(np.float64) + b32.astype(np.float64))


def test_two_prod_is_bitwise_error_free():
    """Dekker TwoProd with the f32 splitter 4097 = 2^12 + 1: p is
    EXACTLY fl(a*b) and p + e is EXACTLY a * b (a 48-bit product, f64-
    representable)."""
    from repro.core.precision import two_prod
    rng = np.random.default_rng(12)
    a32 = (rng.normal(size=4_096) *
           10.0 ** rng.uniform(-6, 6, 4_096)).astype(np.float32)
    b32 = (rng.normal(size=4_096) *
           10.0 ** rng.uniform(-6, 6, 4_096)).astype(np.float32)
    p, e = two_prod(jnp.asarray(a32), jnp.asarray(b32))
    p, e = np.asarray(p), np.asarray(e)
    np.testing.assert_array_equal(p, a32 * b32)          # p == fl(a*b)
    np.testing.assert_array_equal(
        p.astype(np.float64) + e.astype(np.float64),
        a32.astype(np.float64) * b32.astype(np.float64))


def test_fast_two_sum_exact_when_ordered():
    """Dekker FastTwoSum is error-free under its |a| >= |b| premise —
    the dd renormalisation step."""
    from repro.core.precision import fast_two_sum
    rng = np.random.default_rng(13)
    a32 = (rng.normal(size=2_048) * 1e4).astype(np.float32)
    b32 = rng.normal(size=2_048).astype(np.float32)     # |b| << |a|
    s, e = fast_two_sum(jnp.asarray(a32), jnp.asarray(b32))
    np.testing.assert_array_equal(
        np.asarray(s).astype(np.float64) +
        np.asarray(e).astype(np.float64),
        a32.astype(np.float64) + b32.astype(np.float64))


def test_f64_budget_auto_resolves_mma_dd(fresh_plan_registry):
    """Under the f64-equivalent tier (accum_dtype=f64, budget 1e-10%)
    every f32-scalar engine is either policy-illegal or over budget in
    the model, so method='auto' provably resolves the dd family —
    asserted via plan-key inspection (the template of
    test_budget_constrained_auto_resolves_mma_ec, one tier down)."""
    from repro.core.precision import F64_EQUIVALENT, dd_value
    autotune.reset_default_registry()
    n = 1 << 20
    # the premise, in the model's own terms: the best compensated
    # engine floors six decades above the dd budget
    assert autotune.model_percent_error(
        autotune.ReductionPlan(method="mma_ec", split_words=3),
        n, jnp.float32) > 1e-10
    assert autotune.model_percent_error(
        autotune.ReductionPlan(method="mma_dd"), n, jnp.float32) <= 1e-10
    x = jnp.asarray(uniform_input(n, seed=5).astype(np.float32))
    out = ci.reduce_sum(x, method="auto", precision=F64_EQUIVALENT)
    assert out.shape == (2,)                 # the (hi, lo) pair
    reg = autotune.default_registry()
    key = autotune.plan_key("reduce_sum", n, jnp.float32,
                            policy=F64_EQUIVALENT)
    plan = reg.get(key)
    assert plan is not None, [k for k, _ in reg.items()]
    assert plan.method in ("mma_dd", "pallas_dd"), plan
    assert plan.error_pct is not None and plan.error_pct <= 1e-10
    # and the pair is worth carrying: f64-equivalent vs the oracle
    err = percent_error(dd_value(out),
                        np.asarray(x).astype(np.float64))
    assert err <= 1e-10, err
    autotune.reset_default_registry()


def test_dd_refusals_name_the_reason():
    """The dd family is policy-gated both ways: without a policy the
    engines refuse (they return a pair, not the default f32 scalar);
    under the f64 policy every scalar engine refuses naming
    accum_dtype — and the legal set is exactly the dd family."""
    from repro.core.precision import F64_EQUIVALENT
    x = jnp.ones((4_096,), jnp.float32)
    for eng in ("mma_dd", "pallas_dd"):
        with pytest.raises(ValueError, match="hi, lo"):
            ci.reduce_sum(x, method=eng)
        with pytest.raises(ValueError, match="hi, lo"):
            ci.squared_sum(x, method=eng)
    for eng in ("mma", "mma_chained", "pallas", "vpu", "mma_ec"):
        with pytest.raises(ValueError, match="accum_dtype"):
            ci.reduce_sum(x, method=eng, precision=F64_EQUIVALENT)
    spec = dispatch.op_spec("reduce_sum")
    ctx = dispatch.build_context("reduce_sum", x,
                                 policy=F64_EQUIVALENT)
    assert dispatch.legal_engines(spec, ctx) == ("mma_dd", "pallas_dd")


def test_plan_key_prec_lat_mesh_composition():
    """The full suffix grammar composes in its fixed order —
    [engine][|prec:][|lat:][|mesh:] — with the f64-equivalent policy
    in the prec slot."""
    from repro.core.precision import F64_EQUIVALENT
    key = autotune.plan_key("reduce_sum", 2**20, jnp.float32,
                            engine=("mma_dd", "pallas_dd"),
                            policy=F64_EQUIVALENT,
                            objective=0.25, mesh="data4.model2")
    assert key.endswith("|mma_dd+pallas_dd"
                        "|prec:any.float64.b1e-10"
                        "|lat:slo0.25ms|mesh:data4.model2"), key
    # each suffix is independent: dropping the objective drops |lat:
    no_lat = autotune.plan_key("reduce_sum", 2**20, jnp.float32,
                               engine=("mma_dd", "pallas_dd"),
                               policy=F64_EQUIVALENT,
                               mesh="data4.model2")
    assert "|lat:" not in no_lat and "|prec:" in no_lat, no_lat

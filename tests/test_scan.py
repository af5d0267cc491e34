"""Tests for the triangular-MMA scan & segmented-reduction subsystem.

Covers the ISSUE-2 acceptance surface:
  * parity: tc_scan == jnp.cumsum and tc_segment_reduce ==
    jax.ops.segment_sum within f32-accumulation tolerance on every
    shipped shape, including n < m^2, ragged last tiles, empty
    segments, and bf16/f16 inputs against the f32 accumulator contract;
  * engines: the Pallas kernels match the pure-jnp oracles, and every
    plan the autotuner can emit for the scan/segment families executes
    correctly;
  * dispatch: method='auto' resolves scan plans through the
    PlanRegistry and matches the explicit methods;
  * consumers: the log-space cumprod and the chunked linear recurrence
    match their sequential references.

Property-based cases run when ``hypothesis`` is installed; a
deterministic parametrized subset runs everywhere (the conftest
pattern), so the scan engine is never untested on a hypothesis-less
install.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on environment
    HAVE_HYPOTHESIS = False

from repro.core import autotune, cumsum, masked_cumsum, segment_sum
from repro.core.scan import (tc_cumprod, tc_linear_recurrence, tc_scan,
                             tc_segment_reduce)
from repro.kernels import mma_scan, mma_segment_sum
from repro.kernels import ref

# n < m^2 (= 16384), the group boundary chain*m, and ragged last tiles.
EDGE_SIZES = [1, 7, 127, 128, 129, 511, 4096, 16_385, 70_001]


def _tol(dtype, n):
    if dtype == jnp.float32:
        return 1e-4 * max(np.sqrt(n), 1)
    return 3e-2 * max(np.sqrt(n), 1)  # bf16/f16 inputs, f32 accumulators


def _check_scan_matches_cumsum(n, seed, dtype=jnp.float32, **kw):
    x = np.random.default_rng(seed).normal(size=n).astype(np.float32)
    xj = jnp.asarray(x).astype(dtype)
    got = np.asarray(tc_scan(xj, **kw))
    want = np.cumsum(np.asarray(xj.astype(jnp.float32)),
                     dtype=np.float64)
    np.testing.assert_allclose(got, want, atol=_tol(dtype, n), rtol=1e-2)


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=1, max_value=70_000),
           st.integers(0, 2**31))
    def test_tc_scan_matches_cumsum(n, seed):
        _check_scan_matches_cumsum(n, seed)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(min_value=1, max_value=20_000),
           st.integers(1, 5), st.integers(0, 2**31))
    def test_tc_scan_chain_invariance(n, chain, seed):
        _check_scan_matches_cumsum(n, seed, chain=chain)


@pytest.mark.parametrize("n", EDGE_SIZES)
def test_tc_scan_matches_cumsum_cases(n):
    _check_scan_matches_cumsum(n, seed=n)


@pytest.mark.parametrize("n,chain", [(1, 1), (129, 2), (511, 5),
                                     (16_385, 3)])
def test_tc_scan_chain_cases(n, chain):
    _check_scan_matches_cumsum(n, seed=n, chain=chain)


@pytest.mark.parametrize("n", [127, 4096, 70_001])
@pytest.mark.parametrize("variant", ["single_pass", "recurrence"])
def test_tc_scan_variants(n, variant):
    _check_scan_matches_cumsum(n, seed=n, variant=variant, m=32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("n", [129, 16_385])
def test_tc_scan_low_precision_inputs(n, dtype):
    """bf16/f16 inputs ride f32 accumulators: the error must stay at
    input-rounding scale, far below what low-precision partials give."""
    _check_scan_matches_cumsum(n, seed=n, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(n).normal(size=n)
                    .astype(np.float32)).astype(dtype)
    assert tc_scan(x).dtype == jnp.float32  # contract: f32 out


def test_tc_scan_exclusive_and_axis():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3, 5, 61)).astype(np.float32)
    xj = jnp.asarray(x)
    for axis in (0, 1, 2, -1):
        got = np.asarray(tc_scan(xj, axis=axis))
        np.testing.assert_allclose(got, np.cumsum(x, axis=axis),
                                   atol=1e-4, rtol=1e-5)
    ex = np.asarray(tc_scan(xj, axis=1, inclusive=False))
    want = np.cumsum(x, axis=1) - x
    np.testing.assert_allclose(ex, want, atol=1e-4)
    assert float(tc_scan(jnp.ones((1,)), inclusive=False)[0]) == 0.0


def test_tc_cumprod_matches_cumprod():
    rng = np.random.default_rng(6)
    w = rng.uniform(0.0, 1.0, size=(2, 7, 33)).astype(np.float32)
    w[0, 2, 5] = 0.0  # exact zero: no NaN, zeros propagate
    got = np.asarray(tc_cumprod(jnp.asarray(w), axis=-1))
    np.testing.assert_allclose(got, np.cumprod(w, axis=-1), atol=1e-5)
    assert not np.isnan(got).any()
    ex = np.asarray(tc_cumprod(jnp.asarray(w), axis=-1,
                               inclusive=False))
    ref_ex = np.cumprod(np.concatenate(
        [np.ones_like(w[..., :1]), w[..., :-1]], axis=-1), axis=-1)
    np.testing.assert_allclose(ex, ref_ex, atol=1e-5)


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_tc_linear_recurrence_matches_sequential(chunk):
    rng = np.random.default_rng(chunk)
    B, S, W = 2, 37, 5
    log_a = -np.abs(rng.normal(size=(B, S, W))).astype(np.float32)
    b = rng.normal(size=(B, S, W)).astype(np.float32)
    h0 = rng.normal(size=(B, W)).astype(np.float32)
    a = np.exp(log_a)
    want = np.zeros((B, S, W))
    h = h0.copy()
    for t in range(S):
        h = a[:, t] * h + b[:, t]
        want[:, t] = h
    hs, hf = tc_linear_recurrence(jnp.asarray(log_a), jnp.asarray(b),
                                  jnp.asarray(h0), chunk=chunk)
    np.testing.assert_allclose(np.asarray(hs), want, atol=3e-5)
    np.testing.assert_allclose(np.asarray(hf), want[:, -1], atol=3e-5)


# ------------------------------------------------------- segmented


def test_segment_reduce_basic_and_empty_segments():
    rng = np.random.default_rng(7)
    v = rng.normal(size=997).astype(np.float32)
    ids = rng.integers(0, 13, size=997)
    ids[ids == 5] = 6  # segment 5 is empty
    got = np.asarray(tc_segment_reduce(jnp.asarray(v), jnp.asarray(ids),
                                       16))
    want = np.zeros(16)
    np.add.at(want, ids, v.astype(np.float64))
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert got[5] == 0.0 and (got[13:] == 0.0).all()
    # zero-size edges
    assert tc_segment_reduce(jnp.zeros((0,)), jnp.zeros((0,), jnp.int32),
                             4).shape == (4,)
    assert tc_segment_reduce(v, jnp.asarray(ids), 0).shape == (0,)


def test_segment_reduce_sorted_is_block_diagonal_case():
    """Contiguous (sorted) ids — the paper-style block-diagonal mask."""
    v = np.arange(1, 9, dtype=np.float32)
    ids = np.asarray([0, 0, 0, 1, 1, 2, 2, 2])
    got = np.asarray(tc_segment_reduce(jnp.asarray(v), jnp.asarray(ids),
                                       3))
    np.testing.assert_allclose(got, [6.0, 9.0, 21.0])


def test_segment_reduce_many_segments_blocked_path():
    """Large num_segments shrinks the mask block: the lax.scan
    multi-block path must agree with the one-shot contraction."""
    rng = np.random.default_rng(9)
    n, s = 10_000, 65_536  # block = 128 -> ~79 scanned blocks
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, s, size=n).astype(np.int32))
    got = np.asarray(tc_segment_reduce(v, ids, s))
    want = np.asarray(ref.segment_sum_ref(v, ids, s))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_segment_reduce_int_values():
    got = np.asarray(tc_segment_reduce(
        jnp.asarray([1, 2, 3, 4], jnp.int32),
        jnp.asarray([0, 1, 0, 1], jnp.int32), 2))
    np.testing.assert_allclose(got, [4.0, 6.0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_reduce_matches_jax_ops(dtype):
    rng = np.random.default_rng(8)
    v = jnp.asarray(rng.normal(size=4321).astype(np.float32)) \
        .astype(dtype)
    ids = jnp.asarray(rng.integers(0, 64, size=4321).astype(np.int32))
    got = np.asarray(tc_segment_reduce(v, ids, 64))
    want = np.asarray(jax.ops.segment_sum(
        np.asarray(v.astype(jnp.float32)), np.asarray(ids),
        num_segments=64))
    np.testing.assert_allclose(got, want, atol=2e-1 if
                               dtype == jnp.bfloat16 else 1e-3)


# ------------------------------------------------------- kernels


@pytest.mark.parametrize("n", [1, 129, 128 * 128, 128 * 128 * 2 + 13])
@pytest.mark.parametrize("chain,block_rows", [(1, 8), (2, 32), (4, 128)])
def test_mma_scan_kernel_matches_oracle(n, chain, block_rows):
    rng = np.random.default_rng(n + chain)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = np.asarray(mma_scan(x, chain=chain, block_rows=block_rows))
    want = np.asarray(ref.scan_ref(x))
    np.testing.assert_allclose(got, want, atol=_tol(jnp.float32, n),
                               rtol=1e-5)
    ex = np.asarray(mma_scan(x, inclusive=False, chain=chain,
                             block_rows=block_rows))
    np.testing.assert_allclose(ex, np.asarray(
        ref.scan_ref(x, inclusive=False)),
        atol=_tol(jnp.float32, n), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_mma_scan_kernel_low_precision(dtype):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=20_000).astype(np.float32)) \
        .astype(dtype)
    got = np.asarray(mma_scan(x, chain=2, block_rows=32))
    want = np.asarray(ref.scan_ref(x))
    np.testing.assert_allclose(got, want, atol=_tol(dtype, 20_000),
                               rtol=2e-2)


def test_mma_segment_sum_kernel_matches_oracle():
    rng = np.random.default_rng(12)
    v = jnp.asarray(rng.normal(size=3777).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 19, size=3777).astype(np.int32))
    got = np.asarray(mma_segment_sum(v, ids, 19, block_rows=8))
    want = np.asarray(ref.segment_sum_ref(v, ids, 19))
    np.testing.assert_allclose(got, want, atol=1e-3)
    # ragged pad slots (id -1) must not leak into any segment
    assert got.shape == (19,)


def test_mma_segment_sum_clamps_mask_to_vmem():
    """A large segment count fits VMEM at the default row tile: the
    kernel holds an (S, m) accumulator, never a (block_rows*m, S)
    one-hot mask."""
    rng = np.random.default_rng(19)
    s = 4096  # default block_rows=128 would need a 256MB mask tile
    v = jnp.asarray(rng.normal(size=2_000).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, s, size=2_000).astype(np.int32))
    got = np.asarray(mma_segment_sum(v, ids, s))
    want = np.asarray(ref.segment_sum_ref(v, ids, s))
    np.testing.assert_allclose(got, want, atol=1e-4)


# ------------------------------------------------------- dispatch


def test_every_emittable_scan_plan_matches(fresh_plan_registry):
    rng = np.random.default_rng(13)
    for n in (387, 16_384):
        x = jnp.asarray(rng.normal(size=n).astype(np.float32))
        want = np.cumsum(np.asarray(x), dtype=np.float64)
        for plan in autotune.candidate_plans(n, x.dtype, op="scan"):
            got = np.asarray(autotune.execute_plan(x, plan, op="scan"))
            np.testing.assert_allclose(
                got, want, atol=_tol(jnp.float32, n), rtol=1e-4,
                err_msg=str(plan))


def test_every_emittable_segment_plan_matches(fresh_plan_registry):
    rng = np.random.default_rng(14)
    n = 5_000
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 37, size=n).astype(np.int32))
    want = np.asarray(ref.segment_sum_ref(v, ids, 37))
    for plan in autotune.candidate_plans(n, v.dtype, op="segment_sum"):
        got = np.asarray(autotune.execute_plan(
            v, plan, op="segment_sum", segment_ids=ids, num_segments=37))
        np.testing.assert_allclose(got, want, atol=1e-3,
                                   err_msg=str(plan))


def test_auto_resolves_scan_plans_through_registry(fresh_plan_registry):
    """method='auto' must execute exactly what the registry holds for
    the op='scan' key — seed a deliberately non-default plan."""
    reg = fresh_plan_registry
    x = jnp.asarray(np.random.default_rng(15)
                    .normal(size=3_000).astype(np.float32))
    forced = autotune.ReductionPlan(method="mma_chained", chain=5)
    autotune._default_registry = reg  # route the default-registry path
    try:
        reg.put(autotune.plan_key("scan", x.size, x.dtype), forced)
        assert autotune.get_plan(x.size, x.dtype, op="scan",
                                 registry=reg) == forced
        got = np.asarray(cumsum(x, method="auto"))
        np.testing.assert_allclose(got, np.cumsum(np.asarray(x)),
                                   atol=1e-3)
        # the auto call hit the seeded key, not a fresh sweep
        assert reg.get(autotune.plan_key("scan", x.size,
                                         x.dtype)) == forced
    finally:
        autotune.reset_default_registry()


def test_integration_auto_matches_explicit(fresh_plan_registry):
    rng = np.random.default_rng(16)
    x = jnp.asarray(rng.normal(size=2_048).astype(np.float32))
    mask = jnp.asarray((rng.random(2_048) > 0.5).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(cumsum(x, method="auto")),
        np.asarray(cumsum(x, method="mma")), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(masked_cumsum(x, mask, method="auto")),
        np.asarray(masked_cumsum(x, mask, method="mma")),
        rtol=1e-5, atol=1e-3)
    ids = jnp.asarray(rng.integers(0, 11, 2_048).astype(np.int32))
    np.testing.assert_allclose(
        np.asarray(segment_sum(x, ids, 11, method="auto")),
        np.asarray(segment_sum(x, ids, 11, method="mma")),
        rtol=1e-5, atol=1e-3)
    # the registry now holds scan-family keys
    keys = [k for k, _ in autotune.default_registry().items()]
    assert any(k.startswith("scan|") for k in keys)
    assert any(k.startswith("segment_sum|") for k in keys)


def test_scan_auto_inside_jit(fresh_plan_registry):
    x = jnp.asarray(np.random.default_rng(17)
                    .normal(size=1_024).astype(np.float32))
    f = jax.jit(lambda v: cumsum(v, method="auto"))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.cumsum(np.asarray(x)),
                               rtol=1e-5, atol=1e-3)


def test_kernel_auto_spelling_tunes_per_engine(fresh_plan_registry):
    x = jnp.asarray(np.random.default_rng(18)
                    .normal(size=40_000).astype(np.float32))
    got = np.asarray(mma_scan(x, chain="auto", block_rows="auto"))
    np.testing.assert_allclose(got, np.cumsum(np.asarray(x)), atol=1e-2)
    keys = dict(autotune.default_registry().items())
    pallas_keys = [k for k in keys
                   if k.startswith("scan|") and k.endswith("|pallas")]
    assert pallas_keys
    assert all(keys[k].method == "pallas" for k in pallas_keys)


def test_scan_grad():
    """Scans feed training-time consumers (decays, offsets) — the
    pure-JAX core must be differentiable."""
    g = jax.grad(lambda v: tc_scan(v)[-1])(jnp.ones((300,), jnp.float32))
    np.testing.assert_allclose(np.asarray(g), 1.0, rtol=1e-6)

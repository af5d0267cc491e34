"""The paper's contribution as a composable JAX module.

``tc_reduce`` implements the chained-MMA arithmetic reduction of
Navarro et al. (2020) in pure ``jax.lax`` ops, structured so that every
partial-summation is an *actual matrix multiply against a ones matrix*
(``lax.dot_general`` with f32 accumulation), i.e. on TPU it is routed to
the MXU exactly as the paper routes it to tensor cores.  This module is
safe under ``jit``/``pjit``/``shard_map`` and is what the framework's
higher layers (loss, grad-norm, router stats) call on every training
step; the hand-tiled Pallas version lives in ``repro.kernels``.

Shape convention: the input is flattened, zero-padded to a multiple of
``chain * m * m`` and viewed as groups of ``chain`` m x m matrices:

    X -> (G, chain, m, m)
    C_g = sum_r  [1]_{1 x m} x M_{g,r}        (chain of MMAs, f32 accum)
    s_g = C_g x [1]_{m x 1}                   (final transposed MMA)

followed by variant-specific combining of the per-group scalars s_g.
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import (ACCUM_DTYPE, compensated_sum,
                                  dd_from_any, fast_two_sum,
                                  split_f32_words, two_prod)

DEFAULT_M = 128  # MXU tile (the paper's m; m=4 at GPU hw level, 16 in wmma)

Variant = Literal["single_pass", "recurrence", "split"]


def _as_groups(x, chain: int, m: int):
    """Flatten + zero-pad to (G, chain, m, m)."""
    flat = jnp.ravel(x)
    n = flat.shape[0]
    per_group = chain * m * m
    g = int(math.ceil(max(n, 1) / per_group))
    padded = g * per_group
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(g, chain, m, m)


def _mma_chain(groups, *, accum_dtype=ACCUM_DTYPE):
    """C_g = sum_r [1]_{1xm} x M_{g,r}; returns (G, m) f32 row-accumulators.

    The ones-row matmul is expressed as a dot_general so XLA lowers it to
    the matrix unit; accumulation dtype is pinned to f32 (the paper's
    FP32 C/D accumulators).
    """
    g, chain, m, _ = groups.shape
    ones_row = jnp.ones((1, m), dtype=groups.dtype)
    # (1, m) x (G, chain, m, m) -> (G, chain, 1, m): batched ones-MMA.
    prod = lax.dot_general(
        ones_row, groups,
        dimension_numbers=(((1,), (2,)), ((), ())),
        preferred_element_type=accum_dtype,
    )  # -> (1, G, chain, m)
    # The chain accumulation C_r = [1] x M_r + C_{r-1}:
    return jnp.sum(prod[0], axis=1)  # (G, m) f32


def _mma_collapse(acc, *, cast_to=None):
    """s_g = C_g x [1]_{m x 1} (the final transposed MMA). (G, m) -> (G,)."""
    m = acc.shape[-1]
    a = acc if cast_to is None else acc.astype(cast_to)
    ones_col = jnp.ones((m, 1), dtype=a.dtype)
    out = lax.dot_general(
        a, ones_col,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=ACCUM_DTYPE,
    )
    return out[:, 0]


def tc_reduce(x, *, variant: Variant = "single_pass",
              chain: int | str = 4, m: int = DEFAULT_M,
              mma_fraction: float = 0.5,
              keep_f32_partials: bool = True) -> jax.Array:
    """Arithmetic reduction R(X) via chained ones-MMAs. Returns f32 scalar.

    Default geometry: ``chain=4`` (the paper's experimentally-best R on
    small blocks, Figs. 3/5) and ``m=128`` (``DEFAULT_M``, the TPU MXU
    tile — the analogue of the paper's m=4 hardware / m=16 wmma tile);
    the default ``variant='single_pass'`` is the paper's chosen variant.
    ``chain='auto'`` resolves the chain length from the autotuner's plan
    registry for this (n, dtype, backend) instead of a call-site
    constant (resolution uses only trace-time shape/dtype info, so it is
    jit-safe).

    variant='single_pass' (paper §5.2): one chained-MMA level, per-group
      scalars combined in f32 (the atomics stage of the paper).  Partials
      never leave f32 — no overflow/precision cliff.
    variant='recurrence' (paper §5.1/Alg.1): the per-group scalars are
      *re-fed as input values* for the next MMA level until one group
      remains.  With ``keep_f32_partials=False`` the partials are cast
      back to the input dtype between levels — this reproduces the
      paper's recurrence-variant pathology (FP16 overflow on GPUs; bf16
      precision loss here).
    variant='split' (paper §5.3): fraction ``mma_fraction`` of the data
      reduced by MMA chains, the rest by a plain VPU sum.
    """
    if chain == "auto":
        from repro.core import autotune
        chain = autotune.get_plan(x.size, x.dtype, op="reduce_sum",
                                  engine="mma_chained").chain
    return _tc_reduce_impl(x, variant=variant, chain=int(chain), m=m,
                           mma_fraction=mma_fraction,
                           keep_f32_partials=keep_f32_partials)


@functools.partial(jax.jit, static_argnames=(
    "variant", "chain", "m", "mma_fraction", "keep_f32_partials"))
def _tc_reduce_impl(x, *, variant: Variant, chain: int, m: int,
                    mma_fraction: float,
                    keep_f32_partials: bool) -> jax.Array:
    in_dtype = x.dtype
    if variant == "split":
        flat = jnp.ravel(x)
        n = flat.shape[0]
        n_mma = int(n * mma_fraction)
        mma_part = tc_reduce(flat[:n_mma], variant="single_pass",
                             chain=chain, m=m)
        vpu_part = jnp.sum(flat[n_mma:].astype(jnp.float32))
        return mma_part + vpu_part

    groups = _as_groups(x, chain, m)
    acc = _mma_chain(groups)
    scalars = _mma_collapse(acc)  # (G,) f32

    if variant == "single_pass":
        # Block results combined on f32 accumulators (atomic-add analogue).
        return jnp.sum(scalars)

    if variant == "recurrence":
        # Python loop: G shrinks by chain*m^2 each level; trace-time bound.
        while scalars.shape[0] > 1:
            nxt = scalars if keep_f32_partials else scalars.astype(in_dtype)
            groups = _as_groups(nxt, chain, m)
            acc = _mma_chain(groups)
            scalars = _mma_collapse(acc)
        return scalars[0]

    raise ValueError(f"unknown variant: {variant!r}")


def tc_reduce_ec(x, *, split_words: int = 2, chain: int | str = 2,
                 m: int = DEFAULT_M) -> jax.Array:
    """Error-compensated reduction: split-bf16 MMA chains + TwoSum
    combine.  Returns an f32 scalar at (near) correctly-rounded
    accuracy.

    The ``mma_ec`` engine family (paper §5.4 extended per Markidis et
    al., arXiv:1803.04014): each f32 multiplicand is split into
    ``split_words`` bf16 words (``repro.core.precision.
    split_f32_words`` — 3 words reconstruct f32 exactly, 2 keep ~16
    bits), one ones-MMA chain runs per word with f32 accumulators
    exactly like ``tc_reduce``, and the per-lane f32 partials of every
    word are folded with the pairwise-TwoSum compensated tree
    (``repro.core.precision.compensated_sum``) instead of the plain
    final MMA — so the combine stage is error-free to first order and
    the result is the correctly-rounded f32 sum up to the words'
    representation residual.  ``chain='auto'`` resolves the geometry
    from the autotuner's plan registry (engine ``'mma_ec'``).
    """
    if chain == "auto":
        from repro.core import autotune
        chain = autotune.get_plan(x.size, x.dtype, op="reduce_sum",
                                  engine="mma_ec").chain
    return _tc_reduce_ec_impl(x, split_words=int(split_words),
                              chain=int(chain), m=m)


@functools.partial(jax.jit, static_argnames=("split_words", "chain", "m"))
def _tc_reduce_ec_impl(x, *, split_words: int, chain: int,
                       m: int) -> jax.Array:
    words = split_f32_words(x, split_words)
    # One MMA chain per word; keep the (G, m) f32 lane partials — the
    # final transposed MMA is replaced by the compensated combine, so
    # no partial is ever re-rounded through a second contraction.
    lanes = [jnp.ravel(_mma_chain(_as_groups(w, chain, m)))
             for w in words]
    return compensated_sum(jnp.concatenate(lanes))


def _dd_merge_tree(hi, lo):
    """Pairwise double-double merge tree; returns the final (hi, lo).

    Each halving level adds adjacent high words with a *pair-granular
    ones-MMA*: a dot_general over a trailing axis of size 2 rounds
    exactly once, so it is bit-identical to ``fl(a + b)`` and the
    TwoSum residual computed on the VPU stays exact through the matrix
    unit (the arXiv:2607.06881 trick at the smallest tile).  Low words
    fold into the residual and the pair renormalises with FastTwoSum,
    so each level contributes only O(eps32^2) relative error —
    ~log2(n) * eps32^2 total, f64-equivalent for any practical n.
    """
    hi = jnp.ravel(hi).astype(ACCUM_DTYPE)
    lo = jnp.ravel(lo).astype(ACCUM_DTYPE)
    if hi.shape[0] == 0:
        z = jnp.zeros((), ACCUM_DTYPE)
        return z, z
    ones_pair = jnp.ones((2,), dtype=ACCUM_DTYPE)
    while hi.shape[0] > 1:
        if hi.shape[0] % 2:
            hi = jnp.pad(hi, (0, 1))
            lo = jnp.pad(lo, (0, 1))
        h2 = hi.reshape(-1, 2)
        a, b = h2[:, 0], h2[:, 1]
        # s = fl(a + b) via the batched pair ones-MMA.
        s = lax.dot_general(
            h2, ones_pair,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=ACCUM_DTYPE)
        # Knuth TwoSum residual of that exact same rounding (VPU side).
        bv = s - a
        av = s - bv
        e = (a - av) + (b - bv)
        l2 = lo.reshape(-1, 2)
        hi, lo = fast_two_sum(s, e + (l2[:, 0] + l2[:, 1]))
    return hi[0], lo[0]


def tc_reduce_dd(x, *, square: bool = False) -> jax.Array:
    """Double-double reduction: returns a shape-(2,) f32 ``[hi, lo]``
    pair whose (exact) sum is the f64-equivalent value of ``sum(x)``
    (or ``sum(x*x)`` with ``square=True``).

    The ``mma_dd`` engine (ROADMAP item 2, arXiv:2607.06881): every
    partial is an unevaluated (hi, lo) f32 pair carried through the
    whole pairwise merge tree via TwoSum/TwoProd — the high-word adds
    ride pair-granular ones-MMAs (see ``_dd_merge_tree``), the
    residuals stay on the VPU.  f64 inputs (under ``jax_enable_x64``)
    split exactly into dd on entry, so input-representation error is
    ~2^-48 relative, not 2^-24.  Collapse the pair with
    ``repro.core.precision.dd_value`` (f64 hi + lo).
    """
    return _tc_reduce_dd_impl(x, square=bool(square))


@functools.partial(jax.jit, static_argnames=("square",))
def _tc_reduce_dd_impl(x, *, square: bool) -> jax.Array:
    hi, lo = dd_from_any(x)
    if square:
        # dd square: (hi + lo)^2 = TwoProd(hi, hi) + 2 hi lo + lo^2.
        p, e = two_prod(hi, hi)
        hi, lo = fast_two_sum(p, e + (2.0 * hi * lo + lo * lo))
    h, l = _dd_merge_tree(hi, lo)
    return jnp.stack([h, l])


def tc_contract(a, b) -> jax.Array:
    """Full contraction <a, b> as one dot_general (f32 accumulation).

    This is the sharding-safe form of the paper's ones-MMA encoding: the
    reduction is expressed as a matrix-unit contraction instead of a
    vector-lane sum, *without reshaping* — so under pjit the partitioner
    lowers it to a local MXU contraction + one psum, no re-layout.  With
    ``b = ones_like(a)`` this is the plain sum; ``b = mask`` gives the
    masked numerator; ``b = a`` the squared sum.
    """
    dims = tuple(range(a.ndim))
    return lax.dot_general(
        a, b, dimension_numbers=((dims, dims), ((), ())),
        preferred_element_type=ACCUM_DTYPE)


def tc_reduce_axes(x, axes: tuple, *, b=None) -> jax.Array:
    """Contraction over an axis subset: sum x*b over ``axes``, f32.

    The batched generalisation of ``tc_contract``/``tc_reduce_lastdim``:
    the reduced axes become the contracting dims of a single dot_general
    and every other axis is a *batch* dim — no reshape, no tile
    padding, so the surviving dims keep exactly the layout (and
    sharding) the caller gave them.  ``b=None`` contracts against a
    ones matrix (the plain batched sum, routed through the proven
    ``tc_reduce_lastdim`` fast path for the last-dim subset); ``b=x``
    gives the batched squared sum.  ``axes`` must be a non-empty tuple
    of non-negative ints; output dims preserve the relative order of
    the surviving axes (``jnp.sum`` semantics, keepdims=False).
    """
    axes = tuple(sorted(axes))
    if b is None:
        if axes == (x.ndim - 1,):
            return tc_reduce_lastdim(x)   # proven reshape-free fast path
        b = jnp.ones_like(x)
    if len(axes) == x.ndim:
        return tc_contract(x, b)
    batch = tuple(i for i in range(x.ndim) if i not in axes)
    return lax.dot_general(
        x, b,
        dimension_numbers=((axes, axes), (batch, batch)),
        preferred_element_type=ACCUM_DTYPE)


def tc_sum(x, axes=None) -> jax.Array:
    """Sum of ``x`` over ``axes`` (None = every element), f32: the
    ones-contraction as one compiled program that reads ``x`` once.

    The ones are made inside the program, so XLA fuses them into the
    contraction; an eager ``tc_contract(x, ones_like(x))`` first writes
    a whole array of ones to memory and then reads it back beside ``x``.
    ``axes`` is a sorted tuple of non-negative ints (``tc_reduce_axes``).
    Under an outer ``jit`` the program is inlined.
    """
    return _contraction(x, axes=axes, square=False, contract=tc_contract)


def tc_squared_sum(x, axes=None) -> jax.Array:
    """Sum of ``x * x`` over ``axes`` (None = every element), f32: the
    contraction of ``x`` with itself as one compiled program whose only
    parameter is ``x``.  An eager ``tc_contract(x, x)`` is a program of
    two parameters, which loads the same buffer twice.
    """
    return _contraction(x, axes=axes, square=True, contract=tc_contract)


@functools.partial(jax.jit, static_argnames=("axes", "square", "contract"))
def _contraction(x, *, axes, square: bool, contract):
    # ``contract`` is static so the program is keyed on the contraction
    # it was traced with: a ``tc_contract`` replaced at run time gets a
    # program of its own, not the one cached for the function it replaced.
    if axes is None:
        return contract(x, x if square else jnp.ones_like(x))
    return tc_reduce_axes(x, axes, b=x if square else None)


@jax.jit
def tc_reduce_lastdim(x) -> jax.Array:
    """Ones-contraction over the last dim: (..., d) -> (...) f32 sums.

    The batched form of the row-wise ones-MMA: no reshape, no tile
    padding — the leading dims stay exactly as the caller (and the
    partitioner) laid them out.  Used by the fused-norm statistic, which
    runs under pjit on activations sharded over (batch, seq): collapsing
    those dims with a reshape forces a re-layout and (on some XLA
    versions) miscompiles inside scan+remat regions, so the fused paths
    must reduce in place.
    """
    ones = jnp.ones((x.shape[-1],), dtype=x.dtype)
    return lax.dot_general(
        x, ones,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=ACCUM_DTYPE)


@functools.partial(jax.jit, static_argnames=("chain", "m"))
def tc_reduce_rows(x2d, *, chain: int = 1, m: int = DEFAULT_M) -> jax.Array:
    """Row-wise MMA reduction: (rows, d) -> (rows,) f32 row sums.

    Used by fused-norm statistics and router load-balance counts — one
    ones-matmul per d//m column tile, accumulated in f32.
    """
    rows, d = x2d.shape
    pad = (-d) % m
    if pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, pad)))
    ones_col = jnp.ones((x2d.shape[1], 1), dtype=x2d.dtype)
    out = lax.dot_general(
        x2d, ones_col,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=ACCUM_DTYPE,
    )
    return out[:, 0]

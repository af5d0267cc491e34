"""Framework hooks: every arithmetic reduction in the training/serving
stack routes through the paper's MMA encoding via these helpers.

Each hook is a thin, semantically-named wrapper over ONE dispatch path
— ``repro.core.dispatch.dispatch(op, x, method=..., **op_kwargs)`` —
where the op's registry entry declares its engines, their capability
predicates, and the autotuner hooks.  There are no per-op ``method``
ladders here (``scripts/check.sh`` enforces that structurally).

``method`` selection:
  'auto'   consult the autotuner's plan registry (repro.core.autotune)
           for this (op, n, dtype, backend) and dispatch to the winning
           engine/geometry — restricted to the engines whose capability
           predicates accept this input and mesh.
  'mma'    pure-JAX ones-contraction (repro.core.reduction) — safe under
           pjit/shard_map, lowers to MXU matmuls on TPU.  Default.
           (For the scan family this spelling is an alias of the
           chained triangular core — a scan has no single-contraction
           form.)
  'mma_chained' the explicitly R-chained tc_reduce/tc_scan cores
           (paper-structured; benchmark/ablation path).
  'pallas' hand-tiled Pallas kernel (repro.kernels) — single-device hot
           paths; interpret=True on CPU.
  'mma_dd' / 'pallas_dd' the double-double family (reduce_sum /
           squared_sum): f64-equivalent (hi, lo) f32 pairs carried via
           TwoSum/TwoProd; returns a shape-(2,) pair, so it is only
           legal under an explicit ``MmaPolicy(accum_dtype=float64)``
           — see docs/precision.md.
  'vpu'    plain jnp ops in f32 — the classic baseline the paper
           compares against (and the ablation switch).

An engine an op does not declare — or one whose predicates reject the
call (axis-subset reductions on a flatten-only engine, Pallas under a
multi-device mesh, a split-word policy on a plain engine, …) — raises
``ValueError`` naming the reason.

Every hook takes ``precision``: ``None`` (the default — current
behaviour, no policy), a ``repro.core.precision.MmaPolicy`` (the
subsystem's policy carrier: multiplicand dtype, accumulator dtype,
split-bf16 word count, error budget), or — backward compatibly — a
bare ``jax.lax.Precision``.  The policy restricts the legal engine
set, keys (and error-budget-constrains) auto plans, and reaches the
engine runners; see docs/precision.md.
"""

from __future__ import annotations

import functools
import math
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch

Method = Literal["auto", "mma", "mma_chained", "mma_ec", "pallas",
                "pallas_ec", "mma_dd", "pallas_dd", "vpu"]


def _norm_axes(axis, ndim: int) -> Optional[tuple]:
    """Normalise an ``axis`` argument to a sorted tuple of non-negative
    ints — or None for a full (flatten) reduction, which every engine
    can serve.  Out-of-range axes raise (``jnp.sum`` semantics), they
    are never silently wrapped; an empty tuple stays empty (reduce
    over no axes)."""
    if axis is None:
        return None
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    for a in axes:
        if not -ndim <= a < ndim:
            raise ValueError(
                f"axis {a} is out of bounds for an ndim-{ndim} input")
    axes = tuple(sorted(a % ndim for a in axes))
    if len(set(axes)) != len(axes):
        raise ValueError(f"duplicate reduction axes: {axis!r}")
    return None if axes and len(axes) == ndim else axes


def _keepdims(out, axes: Optional[tuple], ndim: int, keepdims: bool):
    if not keepdims:
        return out
    if axes is None:
        return jnp.reshape(out, (1,) * ndim)
    return jnp.expand_dims(out, axes)


def reduce_sum(x, *, axis=None, keepdims: bool = False,
               method: Method = "mma", chain: int = 4,
               precision=None, objective=None,
               bucket: str = "pow2") -> jax.Array:
    """Sum over ``axis`` (None = all elements), f32.

    'auto' selects a cached ReductionPlan (engine + chain + block_rows)
    from the autotuner; 'mma' uses the ones-contraction form
    (distribution-safe, and the only MMA engine that serves *batched*
    axis-subset reductions — ``tc_reduce_lastdim`` for the last dim,
    the batched ones-contraction ``tc_reduce_axes`` otherwise); the
    explicitly-chained tc_reduce and the Pallas kernel are the
    flatten-only paper-structured single-device paths.

    ``objective`` (a ``repro.core.autotune.LatencyObjective`` or a
    bare number of milliseconds) makes the 'auto' selection SLO-aware
    and keys the plan with the ``|lat:`` suffix — the serving stack's
    latency knob; explicit methods ignore it.  ``bucket`` names the
    shape-bucketing policy the 'auto' plan is keyed under
    (``repro.core.autotune.bucket_cap``; ``None`` for exact keys).

    >>> float(reduce_sum(jnp.ones((2, 8))))
    16.0
    >>> float(reduce_sum(jnp.arange(4.0), method="vpu"))
    6.0
    >>> import numpy as np
    >>> np.asarray(reduce_sum(jnp.ones((2, 8)), axis=-1)).tolist()
    [8.0, 8.0]
    >>> reduce_sum(jnp.ones((2, 8)), axis=0, keepdims=True).shape
    (1, 8)
    """
    axes = _norm_axes(axis, x.ndim)
    if axes == ():                  # reduce over no axes (jnp semantics)
        return x.astype(jnp.float32)
    out = dispatch.dispatch("reduce_sum", x, method=method, chain=chain,
                            precision=precision, objective=objective,
                            bucket=bucket, axis=axes)
    return _keepdims(out, axes, x.ndim, keepdims)


def reduce_mean(x, *, axis=None, keepdims: bool = False,
                method: Method = "mma", precision=None,
                objective=None) -> jax.Array:
    """Mean over ``axis`` (None = all elements), f32.

    >>> import numpy as np
    >>> np.asarray(reduce_mean(jnp.ones((4, 8)), axis=1)).tolist()
    [1.0, 1.0, 1.0, 1.0]
    """
    axes = _norm_axes(axis, x.ndim)
    count = x.size if axes is None \
        else math.prod(x.shape[a] for a in axes)
    return reduce_sum(x, axis=axis, keepdims=keepdims,
                      method=method, precision=precision,
                      objective=objective) / count


def masked_mean(values, mask, *, method: Method = "mma",
                chain: int = 4, precision=None) -> jax.Array:
    """mean of values where mask==1 — the token-loss reduction.

    In 'mma' form the numerator is a *single* contraction <values, mask>
    (the mask plays the ones-matrix role), and the denominator is
    <mask, ones>.  Every other engine reduces values*mask and mask
    separately under the same plan.  All-masked inputs yield 0 (the
    denominator is floored at 1).

    >>> v = jnp.asarray([1.0, 2.0, 30.0, 40.0])
    >>> m = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    >>> float(masked_mean(v, m))
    1.5
    >>> float(masked_mean(v, jnp.zeros(4)))  # all-masked: denom floor 1
    0.0
    """
    mask = mask.astype(values.dtype)
    return dispatch.dispatch("masked_mean", values, method=method,
                             chain=chain, precision=precision,
                             mask=mask)


def squared_sum(x, *, axis=None, keepdims: bool = False,
                method: Method = "mma", chain: int = 4,
                precision=None, objective=None,
                bucket: str = "pow2") -> jax.Array:
    """sum(x^2) over ``axis`` (None = all) — grad-norm building block.

    'mma' form: <x, x> as one dot_general — the reduction rides the MXU
    with x itself standing in for the ones matrix (batched over the
    surviving axes when ``axis`` is given).  'pallas' uses the
    hand-tiled chained-MMA kernel (kernels.mma_squared_sum).  'auto'
    dispatches whatever engine the plan registry tuned for this size."""
    axes = _norm_axes(axis, x.ndim)
    if axes == ():                  # reduce over no axes (jnp semantics)
        xf = x.astype(jnp.float32)
        return xf * xf
    out = dispatch.dispatch("squared_sum", x, method=method,
                            chain=chain, precision=precision,
                            objective=objective, bucket=bucket,
                            axis=axes)
    return _keepdims(out, axes, x.ndim, keepdims)


def global_norm(tree, *, method: Method = "mma",
                precision=None) -> jax.Array:
    """L2 norm over a pytree (gradient clipping / monitoring).  'auto'
    tunes per leaf — big embedding tables and small biases get their own
    plans."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = functools.reduce(
        jnp.add, [squared_sum(l, method=method, precision=precision)
                  for l in leaves])
    return jnp.sqrt(total)


def cumsum(x, *, axis: int = -1, inclusive: bool = True,
           method: Method = "mma", chain: int = 4,
           precision=None) -> jax.Array:
    """Prefix sum along ``axis``, f32, same shape.

    'mma'/'mma_chained' run the chained triangular-MMA scan
    (``repro.core.scan.tc_scan`` — the Dakkak-style tensor-core scan);
    'pallas' the hand-tiled kernel (flattened-1D inputs only — its
    capability predicate rejects batched inputs); 'vpu' the classic
    ``jnp.cumsum`` baseline; 'auto' dispatches the plan the registry
    tuned for (op='scan', n, dtype, backend) over the legal engines.
    ``inclusive=False`` gives the exclusive scan (leading zero).
    ``precision`` accepts an ``repro.core.precision.MmaPolicy`` (or a
    bare lax precision): pin ``repro.core.precision.EXACT_OFFSETS``
    for integer-exact prefixes on TPU (the MoE dispatch path), or a
    split-word / budget policy to route through the compensated
    ``mma_ec`` scan.
    """
    return dispatch.dispatch("scan", x, method=method, chain=chain,
                             axis=axis, inclusive=inclusive,
                             precision=precision)


def masked_cumsum(values, mask, *, axis: int = -1,
                  inclusive: bool = True,
                  method: Method = "mma", chain: int = 4,
                  precision=None) -> jax.Array:
    """Prefix sum of ``values`` where ``mask == 1`` (masked-out
    positions contribute 0 but still receive the running prefix) — the
    packed-position / token-budget scan.  f32, same shape."""
    masked = values.astype(jnp.float32) * mask.astype(jnp.float32)
    return dispatch.dispatch("masked_cumsum", masked, method=method,
                             chain=chain, axis=axis,
                             inclusive=inclusive, precision=precision)


def segment_sum(values, segment_ids, num_segments: int, *,
                method: Method = "mma", precision=None) -> jax.Array:
    """Segmented sum: out[s] = sum of values where segment_ids == s.

    'mma' contracts against the one-hot segment matrix (block-diagonal
    for sorted ids — ``repro.core.scan.tc_segment_reduce``); 'pallas'
    masks each tile to one segment at a time in-kernel; 'vpu' is the
    ``jax.ops.segment_sum`` scatter-add baseline; 'auto' consults the
    registry under
    op='segment_sum'.  Empty segments are 0.  (num_segments,) f32.
    """
    return dispatch.dispatch("segment_sum", values, method=method,
                             precision=precision,
                             segment_ids=segment_ids,
                             num_segments=num_segments)


def expert_counts(router_probs_onehot, *, method: Method = "mma",
                  precision=None):
    """Tokens-per-expert from a (tokens, experts) one-hot/weight matrix:
    counts = [1]_{1 x T} x onehot — a single ones-MMA (load-balance
    loss).  A row-wise op: its registry entry declares exactly the
    contraction and VPU engines, so any other ``method`` raises
    ``ValueError`` instead of silently misrouting.
    """
    return dispatch.dispatch("expert_counts", router_probs_onehot,
                             method=method, precision=precision)

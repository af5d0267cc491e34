"""Public jit'd wrappers for the Pallas kernels.

These handle flattening, zero-padding to tile boundaries, variant
dispatch, and interpret-mode selection: on a TPU the kernels compile
to Mosaic custom calls; on any other backend (the CPU, where the tests
run) they run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import mma_compensated as _mc
from repro.kernels import mma_reduce as _mr
from repro.kernels import mma_rmsnorm as _rn
from repro.kernels import mma_scan as _ms

MXU_M = _mr.MXU_M


def _should_interpret(interpret):
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _to_tiles(x, tile_rows: int, m: int):
    """Flatten x, zero-pad to a multiple of tile_rows*m, view as (T, m)."""
    flat = jnp.ravel(x)
    n = flat.shape[0]
    per_tile = tile_rows * m
    padded = int(math.ceil(max(n, 1) / per_tile)) * per_tile
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // m, m)


def _resolve_auto(x, chain, block_rows, *, op: str,
                  engine: str = "pallas"):
    """Turn chain/block_rows='auto' into the registry's tuned ints.

    The sweep is restricted to the named Pallas engine so the geometry
    comes from a plan tuned for THIS kernel, not from whatever engine
    won the unrestricted cross-engine sweep."""
    if chain == "auto" or block_rows == "auto":
        from repro.core import autotune
        plan = autotune.get_plan(x.size, x.dtype, op=op, engine=engine)
        if chain == "auto":
            chain = plan.chain
        if block_rows == "auto":
            block_rows = plan.block_rows
    return int(chain), int(block_rows)


def mma_reduce(x, *, variant: str = "single_pass", chain=4,
               block_rows=128, m: int = MXU_M,
               mma_fraction: float = 0.5, interpret=None) -> jax.Array:
    """Sum all elements of ``x`` via chained ones-MMAs. Returns f32 scalar.

    ``chain``/``block_rows`` accept 'auto' to resolve the tile geometry
    from the autotuner's plan registry for this (n, dtype, backend);
    integer values are the paper's explicit R (chain length) and B
    (rows per VMEM sub-tile) knobs.  Defaults: chain=4, block_rows=128,
    m=128 (the MXU tile).

    ``variant`` must be one of exactly these three strings:
      'single_pass'  one kernel pass, sequential-grid f32 VMEM accumulator
                     (paper §5.2 — the paper's chosen variant; ignores
                     ``mma_fraction``).
      'recurrence'   multi-pass: each pass maps n -> n/(chain*block_rows*m)
                     partials until one tile remains (paper §5.1 / Alg. 1).
      'split'        fraction ``mma_fraction`` of every tile on the MXU,
                     remainder on the VPU (paper §5.3; ignores ``chain``
                     — the tile is (block_rows, m) and the split is
                     within it).
    Any other value raises ``ValueError``.
    """
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="reduce_sum")
    return _mma_reduce_impl(x, variant=variant, chain=chain,
                            block_rows=block_rows, m=m,
                            mma_fraction=mma_fraction,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "variant", "chain", "block_rows", "m", "mma_fraction", "interpret"))
def _mma_reduce_impl(x, *, variant: str, chain: int, block_rows: int,
                     m: int, mma_fraction: float, interpret) -> jax.Array:
    itp = _should_interpret(interpret)
    if variant == "single_pass":
        x2d = _to_tiles(x, chain * block_rows, m)
        out = _mr.single_pass_call(x2d, chain=chain, block_rows=block_rows,
                                   interpret=itp)
        return out[0, 0]
    if variant == "recurrence":
        x2d = _to_tiles(x, chain * block_rows, m)
        # Algorithm 1: keep applying KernelMMA until one tile remains.
        while x2d.shape[0] > chain * block_rows:
            parts = _mr.partials_call(x2d, chain=chain,
                                      block_rows=block_rows, interpret=itp)
            x2d = _to_tiles(parts[:, 0, 0], chain * block_rows, m)
        out = _mr.single_pass_call(x2d, chain=chain, block_rows=block_rows,
                                   interpret=itp)
        return out[0, 0]
    if variant == "split":
        x2d = _to_tiles(x, block_rows, m)
        out = _mr.split_call(x2d, block_rows=block_rows,
                             mma_fraction=mma_fraction, interpret=itp)
        return out[0, 0]
    raise ValueError(f"unknown variant: {variant!r}")


def mma_squared_sum(x, *, chain=4, block_rows=128,
                    m: int = MXU_M, interpret=None) -> jax.Array:
    """sum(x^2) via chained ones-MMAs (gradient-norm hot-spot): squares
    on the VPU, row-reduction on the MXU, f32 partials throughout.
    ``chain``/``block_rows`` accept 'auto' (autotuned plan registry)."""
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="squared_sum")
    return _mma_squared_sum_impl(x, chain=chain, block_rows=block_rows,
                                 m=m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "chain", "block_rows", "m", "interpret"))
def _mma_squared_sum_impl(x, *, chain: int, block_rows: int,
                          m: int, interpret) -> jax.Array:
    itp = _should_interpret(interpret)
    x2d = _to_tiles(x, chain * block_rows, m)
    out = _mr.single_pass_call(x2d, chain=chain, block_rows=block_rows,
                               interpret=itp, square=True)
    return out[0, 0]


def mma_ec_reduce(x, *, split_words: int = 2, chain=2, block_rows=128,
                  m: int = MXU_M, interpret=None) -> jax.Array:
    """Compensated split-bf16 reduction (Pallas ``pallas_ec`` engine):
    the kernel twin of ``repro.core.reduction.tc_reduce_ec``.  Splits
    each f32 tile into ``split_words`` bf16 words in-kernel, chains
    one ones-MMA per word, and Kahan-compensates the f32 lane
    accumulators across the sequential grid.  Returns an f32 scalar at
    (near) correctly-rounded accuracy.  ``chain``/``block_rows``
    accept 'auto' (plan registry, engine ``'pallas_ec'``)."""
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="reduce_sum",
                                      engine="pallas_ec")
    return _mma_ec_impl(x, split_words=int(split_words), chain=chain,
                        block_rows=block_rows, m=m, square=False,
                        interpret=interpret)


def mma_ec_squared_sum(x, *, split_words: int = 2, chain=2,
                       block_rows=128, m: int = MXU_M,
                       interpret=None) -> jax.Array:
    """Compensated sum of squares: squares each tile in f32 on the VPU
    before the in-kernel word split, then reduces like
    ``mma_ec_reduce`` (the grad-norm path under a tight error
    budget)."""
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="squared_sum",
                                      engine="pallas_ec")
    return _mma_ec_impl(x, split_words=int(split_words), chain=chain,
                        block_rows=block_rows, m=m, square=True,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "split_words", "chain", "block_rows", "m", "square", "interpret"))
def _mma_ec_impl(x, *, split_words: int, chain: int, block_rows: int,
                 m: int, square: bool, interpret) -> jax.Array:
    itp = _should_interpret(interpret)
    # The in-kernel split consumes f32 tiles whatever the input dtype.
    x2d = _to_tiles(x.astype(jnp.float32), chain * block_rows, m)
    out = _mc.ec_call(x2d, chain=chain, block_rows=block_rows,
                      split_words=split_words, interpret=itp,
                      square=square)
    return out[0, 0]


def mma_dd_reduce(x, *, chain=2, block_rows=128, m: int = MXU_M,
                  interpret=None) -> jax.Array:
    """Double-double reduction (Pallas ``pallas_dd`` engine): the
    kernel twin of ``repro.core.reduction.tc_reduce_dd``.  Splits the
    input into elementwise (hi, lo) f32 dd pairs (exactly, for f64
    inputs under ``jax_enable_x64``), streams them through
    ``kernels.mma_compensated.dd_call``'s per-word TwoSum-compensated
    VMEM accumulator, and returns the f64-equivalent shape-(2,) f32
    ``[hi, lo]`` pair — collapse it with
    ``repro.core.precision.dd_value``.  ``chain``/``block_rows``
    accept 'auto' (plan registry, engine ``'pallas_dd'``)."""
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="reduce_sum",
                                      engine="pallas_dd")
    return _mma_dd_impl(x, chain=chain, block_rows=block_rows, m=m,
                        square=False, interpret=interpret)


def mma_dd_squared_sum(x, *, chain=2, block_rows=128, m: int = MXU_M,
                       interpret=None) -> jax.Array:
    """Double-double sum of squares: in-kernel TwoProd squares each dd
    pair exactly, then reduces like ``mma_dd_reduce``.  Returns the
    shape-(2,) ``[hi, lo]`` pair."""
    chain, block_rows = _resolve_auto(x, chain, block_rows,
                                      op="squared_sum",
                                      engine="pallas_dd")
    return _mma_dd_impl(x, chain=chain, block_rows=block_rows, m=m,
                        square=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "chain", "block_rows", "m", "square", "interpret"))
def _mma_dd_impl(x, *, chain: int, block_rows: int, m: int,
                 square: bool, interpret) -> jax.Array:
    from repro.core.precision import dd_from_any
    itp = _should_interpret(interpret)
    hi, lo = dd_from_any(x)
    hi2d = _to_tiles(hi, chain * block_rows, m)
    lo2d = _to_tiles(lo, chain * block_rows, m)
    out = _mc.dd_call(hi2d, lo2d, chain=chain, block_rows=block_rows,
                      interpret=itp, square=square)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=(
    "chain", "block_rows", "m", "interpret"))
def mma_reduce_partials(x, *, chain: int = 4, block_rows: int = 128,
                        m: int = MXU_M, interpret=None) -> jax.Array:
    """One recurrence level: per-tile f32 partial sums, shape (G,)."""
    itp = _should_interpret(interpret)
    x2d = _to_tiles(x, chain * block_rows, m)
    parts = _mr.partials_call(x2d, chain=chain, block_rows=block_rows,
                              interpret=itp)
    return parts[:, 0, 0]


def mma_scan(x, *, inclusive: bool = True, chain=4, block_rows=128,
             m: int = MXU_M, interpret=None) -> jax.Array:
    """Prefix sum of the *flattened* ``x`` via triangular MMAs (Pallas).

    Returns the f32 inclusive (or exclusive) prefix in ``x``'s original
    shape, scanning in row-major flattened order — the kernel twin of
    ``repro.core.scan.tc_scan`` over a single axis.  For multi-axis /
    batched scans use the pure-JAX core; this kernel owns the 1D
    single-device hot path.  ``chain``/``block_rows`` accept 'auto'
    (autotuned plan registry, op='scan').
    """
    chain, block_rows = _resolve_auto(x, chain, block_rows, op="scan")
    return _mma_scan_impl(x, inclusive=inclusive, chain=chain,
                          block_rows=block_rows, m=m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "inclusive", "chain", "block_rows", "m", "interpret"))
def _mma_scan_impl(x, *, inclusive: bool, chain: int, block_rows: int,
                   m: int, interpret) -> jax.Array:
    itp = _should_interpret(interpret)
    shape = x.shape
    n = x.size
    x2d = _to_tiles(x, chain * block_rows, m)
    out = _ms.scan_call(x2d, chain=chain, block_rows=block_rows,
                        interpret=itp)
    flat = out.reshape(-1)[:n]
    if not inclusive:
        flat = jnp.concatenate([jnp.zeros((1,), flat.dtype), flat[:-1]])
    return flat.reshape(shape)


def mma_segment_sum(values, segment_ids, num_segments: int, *,
                    block_rows=128, m: int = MXU_M,
                    interpret=None) -> jax.Array:
    """Segmented sum via masked ones-MMAs (Pallas).
    ``values``/``segment_ids`` are flattened together; returns
    (num_segments,) f32.  ``block_rows`` accepts 'auto' (autotuned
    plan registry, op='segment_sum')."""
    _, block_rows = _resolve_auto(values, 1, block_rows,
                                  op="segment_sum")
    return _mma_segment_sum_impl(values, segment_ids,
                                 num_segments=int(num_segments),
                                 block_rows=block_rows, m=m,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block_rows", "m", "interpret"))
def _mma_segment_sum_impl(values, segment_ids, *, num_segments: int,
                          block_rows: int, m: int, interpret) -> jax.Array:
    itp = _should_interpret(interpret)
    v2d = _to_tiles(values, block_rows, m)
    # Pad ids with -1: padded slots match no segment.
    ids = jnp.ravel(segment_ids).astype(jnp.int32)
    pad = v2d.size - ids.shape[0]
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    ids2d = ids.reshape(v2d.shape)
    out = _ms.segment_sum_call(v2d, ids2d, num_segments=num_segments,
                               block_rows=block_rows, interpret=itp)
    return out[0, :num_segments]


def _pick_block_rows(rows: int, d: int, vmem_budget: int = 8 * 2**20):
    """Largest power-of-two row tile whose f32 working set fits VMEM."""
    bm = 128
    while bm > 8 and (3 * bm * d * 4) > vmem_budget:
        bm //= 2
    while bm > 1 and rows % bm:
        bm //= 2
    return max(bm, 1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "weight_offset", "interpret"))
def mma_rmsnorm(x, weight, *, eps: float = 1e-6,
                weight_offset: float = 0.0, interpret=None) -> jax.Array:
    """Fused RMSNorm over the last dim of x (any leading dims).

    .. deprecated:: folded behind the ``norm_matmul`` registry entry —
       this wrapper is now the ``fused_pallas`` engine's norm-only
       (``w=None``) form.  New callers should go through
       ``repro.core.dispatch.dispatch('norm_matmul', x, w=None, ...)``
       or ``repro.models.layers.norm_matmul`` (which also fuses the
       *following* matmul via ``kernels/mma_norm_matmul.py``) so
       capability predicates, precision policies, and autotuned plans
       apply; no kernel should be reachable only via a dispatch()
       bypass.
    """
    itp = _should_interpret(interpret)
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = int(math.prod(lead)) if lead else 1
    x2d = x.reshape(rows, d)
    bm = _pick_block_rows(rows, d)
    pad_rows = int(math.ceil(rows / bm)) * bm
    if pad_rows != rows:
        x2d = jnp.pad(x2d, ((0, pad_rows - rows), (0, 0)))
    out = _rn.rmsnorm_call(x2d, weight, eps=eps,
                           weight_offset=weight_offset, block_rows=bm,
                           interpret=itp)
    return out[:rows].reshape(*lead, d)

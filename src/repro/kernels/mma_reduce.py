"""Chained-MMA arithmetic reduction kernels (Pallas / TPU).

TPU-native adaptation of Navarro et al., "GPU Tensor Cores for fast
Arithmetic Reductions" (2020).  The paper encodes the reduction of ``n``
numbers as chains of m x m matrix-multiply-accumulate (MMA) operations on
tensor cores:

    C_r = [1]_{m x m} x M_r + C_{r-1}          (chain of R loads+MMAs)
    out = C_R x [1]_{m x 1}                    (final transposed MMA)

On TPU the matrix unit is the 128x128 MXU, so ``m = 128`` and a "warp
chain" becomes a grid step owning an ``(R * block_rows, 128)`` VMEM tile:
each of the R sub-tiles is folded into an f32 accumulator with one
ones-matmul (this is the MMA chain), and the accumulator is collapsed
with one final ones-matmul.  TPU has no global atomics, so the paper's
"atomic adds of block results" becomes either

  * ``mma_reduce_kernel``    -- a sequential-grid VMEM scratch accumulator
    (single kernel pass; the single-pass variant), or
  * ``mma_partials_kernel``  -- per-block partials written to HBM, reduced
    by further passes (the recurrence variant).

All partials are kept in f32, exactly like the paper's single-pass
variant keeps FP32 sub-results between MMAs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import ACCUM_DTYPE

# The MXU tile size: the TPU analogue of the paper's ``m``.
MXU_M = 128


def _chain_block(x_ref, chain: int, block_rows: int, acc_dtype,
                 square: bool = False):
    """Run the R-chain of ones-MMAs over one (chain*block_rows, m) tile.

    Returns the (1, m) accumulator C_R = sum_r [1] x M_r  (f32).
    This is Eq. (18)-(21) of the paper with m = 128.

    ``square=True`` squares each tile on the VPU before the ones-MMA —
    the gradient-global-norm hot-spot (sum of squares) in one pass.
    """
    m = x_ref.shape[-1]
    in_dtype = x_ref.dtype
    ones_row = jnp.ones((1, block_rows), dtype=in_dtype)
    acc = jnp.zeros((1, m), dtype=acc_dtype)
    for r in range(chain):
        tile = x_ref[r * block_rows:(r + 1) * block_rows, :]
        if square:
            tile = tile * tile
        # C_r = [1] x M_r + C_{r-1}; the dot targets the MXU.
        acc = acc + jnp.dot(ones_row, tile,
                            preferred_element_type=acc_dtype)
    return acc


def _collapse(acc, acc_dtype):
    """Final transposed MMA: (1, m) x (m, 1) -> (1, 1).  Eq. (22)."""
    m = acc.shape[-1]
    ones_col = jnp.ones((m, 1), dtype=acc.dtype)
    return jnp.dot(acc, ones_col, preferred_element_type=acc_dtype)


def mma_reduce_kernel(x_ref, o_ref, acc_ref, *, chain: int,
                      block_rows: int, square: bool = False):
    """Single-pass chained-MMA reduction.

    Grid walks row-tiles of the (T, m) input sequentially; ``acc_ref`` is
    the persistent (1, m) f32 VMEM accumulator standing in for the GPU's
    cross-block atomics.  The final grid step collapses with the
    transposed ones-MMA and writes the (1, 1) scalar.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _chain_block(x_ref, chain, block_rows, jnp.float32,
                                 square=square)

    @pl.when(step == pl.num_programs(0) - 1)
    def _finish():
        o_ref[...] = _collapse(acc_ref[...], jnp.float32)


def mma_partials_kernel(x_ref, o_ref, *, chain: int, block_rows: int):
    """One level of the recurrence variant: each grid step reduces its own
    (chain*block_rows, m) tile to a single f32 partial (R+1 MMAs) and
    stores it to its slot — Algorithm 2 of the paper, with the store
    standing in for ``X[offset / m^2] = C_{0,0}``.  The slot is a
    lane-dense ``(1, 1, m)`` row (the TPU tiles an HBM block's last two
    dims to (8, 128) unless they span the whole array), holding the
    partial in every lane."""
    acc = _chain_block(x_ref, chain, block_rows, jnp.float32)
    o_ref[0] = jnp.broadcast_to(_collapse(acc, jnp.float32), acc.shape)


def mma_split_kernel(x_ref, o_ref, mma_acc_ref, vpu_acc_ref, *,
                     mma_rows: int):
    """Split variant (paper §5.3): rows [0, mma_rows) of every tile are
    reduced with the ones-MMA chain (MXU), the remaining rows with a
    plain vector sum (VPU).  On TPU the MXU and VPU genuinely co-execute
    within a core, which is the paper's simultaneous-units hypothesis."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        mma_acc_ref[...] = jnp.zeros_like(mma_acc_ref)
        vpu_acc_ref[...] = jnp.zeros_like(vpu_acc_ref)

    block = x_ref[...]
    if mma_rows > 0:
        tile = block[:mma_rows, :]
        ones_row = jnp.ones((1, mma_rows), dtype=tile.dtype)
        mma_acc_ref[...] += jnp.dot(ones_row, tile,
                                    preferred_element_type=ACCUM_DTYPE)
    if mma_rows < block.shape[0]:
        rest = block[mma_rows:, :].astype(jnp.float32)
        vpu_acc_ref[...] += jnp.sum(rest, axis=0, keepdims=True)

    @pl.when(step == pl.num_programs(0) - 1)
    def _finish():
        total = _collapse(mma_acc_ref[...], jnp.float32)
        total += jnp.sum(vpu_acc_ref[...], axis=1, keepdims=True)
        o_ref[...] = total


def single_pass_call(x2d, *, chain: int, block_rows: int,
                     interpret: bool = False, square: bool = False):
    """pallas_call wrapper: x2d is (G*chain*block_rows, m) -> (1,1) f32."""
    rows, m = x2d.shape
    tile_rows = chain * block_rows
    grid = rows // tile_rows
    assert grid * tile_rows == rows, (rows, tile_rows)
    kernel = functools.partial(mma_reduce_kernel, chain=chain,
                               block_rows=block_rows, square=square)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, m), jnp.float32)],
        interpret=interpret,
    )(x2d)


def partials_call(x2d, *, chain: int, block_rows: int,
                  interpret: bool = False):
    """pallas_call wrapper: (G*chain*block_rows, m) -> (G, 1, m) f32
    partials, each replicated across its m lanes."""
    rows, m = x2d.shape
    tile_rows = chain * block_rows
    grid = rows // tile_rows
    assert grid * tile_rows == rows, (rows, tile_rows)
    kernel = functools.partial(mma_partials_kernel, chain=chain,
                               block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid, 1, m), jnp.float32),
        interpret=interpret,
    )(x2d)


def split_call(x2d, *, block_rows: int, mma_fraction: float,
               interpret: bool = False):
    """pallas_call wrapper for the split variant: (T, m) -> (1,1) f32."""
    rows, m = x2d.shape
    grid = rows // block_rows
    assert grid * block_rows == rows, (rows, block_rows)
    # Round the MMA share of each tile to sublane (8-row) granularity.
    mma_rows = int(round(mma_fraction * block_rows / 8.0)) * 8
    mma_rows = max(0, min(block_rows, mma_rows))
    kernel = functools.partial(mma_split_kernel, mma_rows=mma_rows)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, m), jnp.float32),
                        pltpu.VMEM((1, m), jnp.float32)],
        interpret=interpret,
    )(x2d)

"""Chained triangular-MMA scan / segmented-sum kernels (Pallas / TPU).

TPU-native adaptation of the scan encoding of Dakkak et al.
("Accelerating Reduction and Scan Using Tensor Core Units") on top of
the chained-MMA machinery of Navarro et al. (2020):

    P   = X x U_m          (per-row inclusive prefix: triangular MMA)
    c   = L' x t           (row carries inside a tile: strictly lower-
                            triangular MMA over the tile's row totals)
    out = P + c + carry    (carry = running total of previous tiles)

The grid walks row-tiles of the (T, m) input sequentially; ``carry`` is
a persistent lane-replicated (1, m) f32 VMEM row standing in for the GPU scan's
cross-block look-back, exactly like ``mma_reduce_kernel``'s accumulator
stands in for cross-block atomics.  A grid step owns a
``(chain * block_rows, m)`` tile and folds its ``chain`` sub-tiles in
sequence (the R-chain).

The segmented-sum kernel masks each tile to one segment at a time and
folds it with the ones-MMA of the reduction into that segment's
accumulator row.

All partials are f32, matching the reduction family's precision
contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import ACCUM_DTYPE


def _tri_ones(k: int, dtype, *, lower_strict: bool = False):
    """U_k (rows <= cols), or the strictly lower L' (rows > cols),
    built from 2D iotas (TPU requires >= 2D iota)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    return ((rows > cols) if lower_strict else (rows <= cols)).astype(dtype)


def _scan_tile(tile, carry_in):
    """Inclusive prefix of one (rows, m) tile in row-major order.

    Returns (prefix, tile_total): the (rows, m) f32 prefix including
    ``carry_in`` and the tile's own f32 total, both lane-replicated
    (1, m) rows for the carry.  Two triangular MMAs: P = X x U_m, then
    row carries via the strictly-lower L' x t, with the row totals t
    replicated across the lanes so the carry MMA stays lane-dense.
    """
    rows, m = tile.shape
    u_m = _tri_ones(m, tile.dtype)
    p = jnp.dot(tile, u_m, preferred_element_type=ACCUM_DTYPE)
    t = jnp.broadcast_to(p[:, m - 1:], p.shape)         # row totals
    l_strict = _tri_ones(rows, ACCUM_DTYPE, lower_strict=True)
    c = jnp.dot(l_strict, t, preferred_element_type=ACCUM_DTYPE)
    total = c[rows - 1:, :] + t[rows - 1:, :]           # (1, m)
    return p + c + carry_in, total


def mma_scan_kernel(x_ref, o_ref, carry_ref, *, chain: int,
                    block_rows: int):
    """Single-pass chained triangular-MMA scan over a (T, m) layout.

    Each grid step scans its ``chain`` (block_rows, m) sub-tiles in
    sequence, threading the running carry; ``carry_ref`` persists the
    carry across grid steps (sequential grid) as a lane-replicated
    (1, m) row — the TPU stores vectors, not scalars, to VMEM.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    carry = carry_ref[...]
    for r in range(chain):
        tile = x_ref[r * block_rows:(r + 1) * block_rows, :]
        p, total = _scan_tile(tile, carry)
        o_ref[r * block_rows:(r + 1) * block_rows, :] = p
        carry = carry + total
    carry_ref[...] = carry


def mma_segment_sum_kernel(v_ref, ids_ref, o_ref, acc_ref, *,
                           num_segments: int):
    """Segmented sum: for each segment, each grid step masks its
    (rows, m) tile to that segment's values and folds them with one
    ones-MMA into the segment's (1, m) row of the (S, m) f32
    accumulator.  The last step collapses the lanes with one more
    ones-MMA into the (1, S) output.  Padded slots carry id -1 and
    match no segment."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[...]
    ids = ids_ref[...]
    ones_row = jnp.ones((1, v.shape[0]), v.dtype)

    def fold(s, carry):
        masked = jnp.where(ids == s, v, jnp.zeros_like(v))
        acc_ref[pl.ds(s, 1), :] += jnp.dot(
            ones_row, masked, preferred_element_type=ACCUM_DTYPE)
        return carry

    jax.lax.fori_loop(0, num_segments, fold, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _finish():
        ones_lanes = jnp.ones((1, acc_ref.shape[1]), ACCUM_DTYPE)
        o_ref[...] = jax.lax.dot_general(
            ones_lanes, acc_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=ACCUM_DTYPE)


def scan_call(x2d, *, chain: int, block_rows: int,
              interpret: bool = False):
    """pallas_call wrapper: (G*chain*block_rows, m) -> same-shape f32
    row-major inclusive prefix."""
    rows, m = x2d.shape
    tile_rows = chain * block_rows
    grid = rows // tile_rows
    assert grid * tile_rows == rows, (rows, tile_rows)
    kernel = functools.partial(mma_scan_kernel, chain=chain,
                               block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, m), jnp.float32)],
        interpret=interpret,
    )(x2d)


def segment_sum_call(v2d, ids2d, *, num_segments: int, block_rows: int,
                     interpret: bool = False):
    """pallas_call wrapper: (G*block_rows, m) values+ids -> (1, S_p)
    f32, S_p = num_segments rounded up to whole 128-lane rows."""
    rows, m = v2d.shape
    grid = rows // block_rows
    assert grid * block_rows == rows, (rows, block_rows)
    s_pad = -(-max(int(num_segments), 1) // 128) * 128
    kernel = functools.partial(mma_segment_sum_kernel,
                               num_segments=num_segments)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, s_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, s_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((s_pad, m), jnp.float32)],
        interpret=interpret,
    )(v2d, ids2d)

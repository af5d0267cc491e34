"""Compensated split-bf16 MMA reduction kernels (Pallas / TPU).

The hand-tiled twin of ``repro.core.reduction.tc_reduce_ec`` — the
``pallas_ec`` engine.  Each grid step owns a ``(chain * block_rows,
m)`` f32 VMEM tile and:

  1. **splits** the tile into ``split_words`` bf16 words in-register
     (round-to-nearest residual splitting,
     ``repro.core.precision.split_f32_words`` semantics — 3 words
     reconstruct f32 exactly);
  2. runs the paper's R-chain of **ones-MMAs per word** with f32
     accumulation (one ``(1, block_rows) x (block_rows, m)`` dot per
     sub-tile — the MXU path);
  3. folds each word's ``(1, m)`` lane partial into a persistent
     per-word VMEM accumulator with **Kahan compensation** (the
     TwoSum carry lives in a second scratch buffer), so the
     sequential-grid accumulation stays error-free to first order no
     matter how many tiles stream through;
  4. on the last step, folds the ``split_words`` lane accumulators
     together and collapses the lanes with a TwoSum tree **on the
     VPU** (not a final MMA — re-rounding the compensated partials
     through another contraction would throw the carries away), then
     takes the Kahan carries back out.

All accumulators are f32 (``repro.core.precision.ACCUM_DTYPE``), per
the paper's single-pass precision contract.

``mma_dd_kernel`` / ``dd_call`` are the double-double twin (the
``pallas_dd`` engine, kernel sibling of
``repro.core.reduction.tc_reduce_dd``): every partial is an
unevaluated (hi, lo) f32 pair carried via TwoSum/TwoProd, the VMEM
accumulator holds one compensated f32 plane per dd word, and the output
is the f64-equivalent ``[hi, lo]`` pair itself (arXiv:2607.06881).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import ACCUM_DTYPE
from repro.kernels.mma_reduce import MXU_M  # noqa: F401  (re-export)


def _split_tile(tile, split_words: int):
    """In-register round-to-nearest bf16 word split of one f32 tile."""
    words = []
    r = tile
    for _ in range(split_words - 1):
        hi = r.astype(jnp.bfloat16)
        words.append(hi)
        r = r - hi.astype(ACCUM_DTYPE)
    words.append(r.astype(jnp.bfloat16))
    return words


def _word_chain(word, chain: int, block_rows: int):
    """R-chain of ones-MMAs over one bf16 word: -> (1, m) f32 lanes."""
    ones_row = jnp.ones((1, block_rows), dtype=word.dtype)
    acc = jnp.zeros((1, word.shape[-1]), dtype=ACCUM_DTYPE)
    for r in range(chain):
        sub = word[r * block_rows:(r + 1) * block_rows, :]
        acc = acc + jnp.dot(ones_row, sub,
                            preferred_element_type=ACCUM_DTYPE)
    return acc


def _two_sum(a, b):
    """Branch-free Knuth TwoSum (the in-kernel copy of
    ``repro.core.precision.two_sum`` — Pallas kernels cannot call the
    traced host helper, but the transform is identical)."""
    s = a + b
    bv = s - a
    av = s - bv
    return s, (a - av) + (b - bv)


def _comp_collapse(vals, err):
    """TwoSum tree over a (1, k) f32 lane vector -> (1, 1): each level
    adds the lower half of the lanes to the upper half (contiguous
    halves: the TPU has no strided lane slice), an odd lane is folded
    into a separate tail, and the rounding errors are summed on the
    side, starting from the (1, 1) correction ``err``.  The result
    rounds once."""
    tail = jnp.zeros((1, 1), dtype=ACCUM_DTYPE)
    while vals.shape[-1] > 1:
        k = vals.shape[-1]
        if k % 2:
            tail, e = _two_sum(tail, vals[:, k - 1:])
            err = err + e
            vals = vals[:, :k - 1]
            k -= 1
        s, e = _two_sum(vals[:, :k // 2], vals[:, k // 2:])
        err = err + jnp.sum(e, axis=-1, keepdims=True)
        vals = s
    s, e = _two_sum(vals, tail)
    return s + (err + e)


def mma_ec_kernel(x_ref, o_ref, acc_ref, carry_ref, *, chain: int,
                  block_rows: int, split_words: int,
                  square: bool = False):
    """Compensated split-bf16 reduction: sequential grid, per-word
    Kahan-compensated (split_words, 1, m) f32 VMEM accumulators."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        carry_ref[...] = jnp.zeros_like(carry_ref)

    tile = x_ref[...].astype(ACCUM_DTYPE)
    if square:
        tile = tile * tile
    for w, word in enumerate(_split_tile(tile, split_words)):
        contrib = _word_chain(word, chain, block_rows)
        # Kahan step: carry holds what the last add rounded in excess.
        acc = acc_ref[w]
        y = contrib - carry_ref[w]
        t = acc + y
        carry_ref[w] = (t - acc) - y
        acc_ref[w] = t

    @pl.when(step == pl.num_programs(0) - 1)
    def _finish():
        # Fold the words lane-wise with TwoSum, then collapse the lanes.
        lanes = acc_ref[0]
        err = -carry_ref[0]
        for w in range(1, split_words):
            lanes, e = _two_sum(lanes, acc_ref[w])
            err = err + e - carry_ref[w]
        # The errors and carries are ~eps * |lanes|: a plain sum of
        # them leaves only second-order error behind.
        o_ref[...] = _comp_collapse(
            lanes, jnp.sum(err, axis=-1, keepdims=True))


def ec_call(x2d, *, chain: int, block_rows: int, split_words: int,
            interpret: bool = False, square: bool = False):
    """pallas_call wrapper: (G*chain*block_rows, m) f32 -> (1, 1) f32."""
    rows, m = x2d.shape
    tile_rows = chain * block_rows
    grid = rows // tile_rows
    assert grid * tile_rows == rows, (rows, tile_rows)
    kernel = functools.partial(mma_ec_kernel, chain=chain,
                               block_rows=block_rows,
                               split_words=split_words, square=square)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), ACCUM_DTYPE),
        scratch_shapes=[pltpu.VMEM((split_words, 1, m), ACCUM_DTYPE),
                        pltpu.VMEM((split_words, 1, m), ACCUM_DTYPE)],
        interpret=interpret,
    )(x2d)


# ----------------------------------- double-double (pallas_dd) kernel

# Dekker's f32 splitter (2^12 + 1) — the in-kernel copy of
# ``repro.core.precision.two_prod``'s constant.
_SPLIT_F32 = 4097.0


def _fast_two_sum(a, b):
    """Dekker FastTwoSum (requires |a| >= |b|): dd renormalisation."""
    s = a + b
    return s, b - (s - a)


def _two_prod(a, b):
    """Dekker TwoProd via the 2^12+1 split (in-kernel copy of
    ``repro.core.precision.two_prod`` — no FMA assumed)."""
    p = a * b
    ta = _SPLIT_F32 * a
    ahi = ta - (ta - a)
    alo = a - ahi
    tb = _SPLIT_F32 * b
    bhi = tb - (tb - b)
    blo = b - bhi
    return p, ((ahi * bhi - p) + ahi * blo + alo * bhi) + alo * blo


def _dd_merge(a, la, b, lb):
    """dd add of two (hi, lo) planes, elementwise.

    The high-word add rounds exactly once — bit-identical to the
    pair-granular ones-MMA the core twin
    (``repro.core.reduction.tc_reduce_dd``) routes through
    ``dot_general`` — so the TwoSum residual computed here is exact;
    both low words fold into it and the pair renormalises."""
    s, e = _two_sum(a, b)
    return _fast_two_sum(s, e + (la + lb))


def _dd_collapse(hi, lo, axis: int):
    """dd merge tree along ``axis`` (0: rows, 1: lanes) down to size 1.

    Halves are contiguous (the TPU has no strided slice).  Rows halve
    while both halves stay 8-row aligned, the remaining 8-row slabs
    (or single rows, for a tile that is not a multiple of 8) fold in
    sequence, and the last slab halves again; lanes halve while even
    and fold any odd remainder in sequence."""
    def part(a, lo_i, hi_i):
        return a[lo_i:hi_i] if axis == 0 else a[:, lo_i:hi_i]

    def halve(h, lw):
        k = h.shape[axis] // 2
        return _dd_merge(part(h, 0, k), part(lw, 0, k),
                         part(h, k, 2 * k), part(lw, k, 2 * k))

    def fold(h, lw, width):
        ah, al = part(h, 0, width), part(lw, 0, width)
        for i in range(width, h.shape[axis], width):
            ah, al = _dd_merge(ah, al, part(h, i, i + width),
                               part(lw, i, i + width))
        return ah, al

    if axis == 0:
        while hi.shape[0] % 16 == 0:
            hi, lo = halve(hi, lo)
        hi, lo = fold(hi, lo, 8 if hi.shape[0] % 8 == 0 else 1)
    while hi.shape[axis] > 1 and hi.shape[axis] % 2 == 0:
        hi, lo = halve(hi, lo)
    return fold(hi, lo, 1)


def mma_dd_kernel(hi_ref, lo_ref, o_ref, acc_ref, *,
                  square: bool = False):
    """Double-double reduction: sequential grid, per-word (hi plane 0 /
    lo plane 1) TwoSum-compensated ``(2, 1, m)`` f32 VMEM accumulator.

    Each grid step reduces its elementwise-dd tile with a pairwise dd
    merge tree over rows (see ``_dd_collapse``) to ``(1, m)`` dd
    lanes, then dd-adds them into the persistent accumulator — the
    generalisation of the ``mma_ec`` kernel's Kahan carry to a full
    double word.  The last step collapses the lanes with the same dd
    tree and writes the unevaluated ``[hi, lo]`` pair (a ``(2, 1)``
    output), never re-rounding it through a final contraction.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hi = hi_ref[...]
    lo = lo_ref[...]
    if square:
        # dd square: (hi + lo)^2 = TwoProd(hi, hi) + 2 hi lo + lo^2.
        p, e = _two_prod(hi, hi)
        hi, lo = _fast_two_sum(p, e + (2.0 * hi * lo + lo * lo))
    hi, lo = _dd_collapse(hi, lo, 0)
    # dd_add the tile's (1, m) lanes into the per-word accumulators.
    nh, nl = _dd_merge(acc_ref[0], acc_ref[1], hi, lo)
    acc_ref[0] = nh
    acc_ref[1] = nl

    @pl.when(step == pl.num_programs(0) - 1)
    def _finish():
        h, low = _dd_collapse(acc_ref[0], acc_ref[1], 1)
        o_ref[...] = jnp.concatenate([h, low], axis=0)


def dd_call(hi2d, lo2d, *, chain: int, block_rows: int,
            interpret: bool = False, square: bool = False):
    """pallas_call wrapper: two (G*chain*block_rows, m) f32 planes
    (elementwise dd hi/lo) -> (2, 1) f32 ``[[hi], [lo]]``."""
    rows, m = hi2d.shape
    tile_rows = chain * block_rows
    grid = rows // tile_rows
    assert grid * tile_rows == rows, (rows, tile_rows)
    kernel = functools.partial(mma_dd_kernel, square=square)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((tile_rows, m), lambda i: (i, 0)),
                  pl.BlockSpec((tile_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, 1), ACCUM_DTYPE),
        scratch_shapes=[pltpu.VMEM((2, 1, m), ACCUM_DTYPE)],
        interpret=interpret,
    )(hi2d, lo2d)

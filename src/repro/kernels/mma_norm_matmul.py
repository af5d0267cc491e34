"""Fused rmsnorm->matmul Pallas kernel: the norm epilogue fusion that
closes ROADMAP item 1 (registered as the ``norm_matmul`` op's
``fused_pallas`` engine in ``repro.core.dispatch``).

The transformer hot path computes ``rmsnorm(x) @ W`` as two ops: a
chained-MMA row statistic, an HBM round trip of the normalized
activations, then a separate XLA matmul.  Because the rms factor is a
per-row *scalar*,

    ``rmsnorm(x) @ W  ==  rstd * ((x * (1 + scale)) @ W)``,

so one kernel pass over the k (feature) axis can accumulate BOTH the
paper's chained ones-MMA sum of squares AND the unnormalized matmul
partials, applying the row scaling once at the end — the normalized
activations never exist in HBM.  A grid cell owns a (row tile, output
tile) pair; per ``block_rows``-sized k-block (the sequential innermost
grid axis) the kernel

  * folds the **row sum of squares** of the raw rows via one
    ``(rows, w) x (w, 128)`` ones-contraction per ``chain`` sub-slice,
    f32 accumulate (``ACCUM_DTYPE``) — exactly the paper's reduction
    encoding — combined across k-blocks with a Kahan carry in VMEM.
    Only a row tile's first output tile computes it; the later ones
    reuse it from VMEM;
  * accumulates the **unnormalized matmul partial**
    ``(x * (1 + scale))_blk @ W_blk`` (and the gate projection for the
    MLP up/gate pair) into an f32 VMEM accumulator;

and at the last k-block computes ``rstd = rsqrt(ms / d + eps)``, scales
the accumulator rows, adds the optional bias, applies the optional
``act(gate) * up`` pairing, and writes the output tile — one kernel,
zero intermediate HBM traffic.  Output tiles of at most ``_N_TILE``
lanes keep VMEM use from growing with ``dout``.  This is the fusion shape
Dakkak et al. (arXiv:1811.09736) identify: the reduction feeds the
consuming GEMM without leaving the TCU kernel.

Covers the block shapes of ``models/transformer.py`` (qkv and MLP
projections) and the MLA absorbed-form decode projections of
``models/mla.py`` (the rms -> ``wq_b`` chain).  Runs in
``interpret=True`` off-TPU like every kernel in this package; see
docs/ARCHITECTURE.md for the paper-to-code map.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import ACCUM_DTYPE
from repro.kernels.ops import _should_interpret

_LANES = 128     # MXU/VPU lane width: k-blocks and dout pad to it
_N_TILE = 512    # most output lanes per grid cell


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _apply_act(g, act):
    if act is None:
        return g
    if act == "silu":
        return jax.nn.silu(g)
    if act == "gelu":
        return jax.nn.gelu(g, approximate=True)
    raise ValueError(f"unknown norm_matmul act: {act!r}")


def _nm_kernel(*refs, blk, chain, d, eps, act, has_gate, has_bias):
    it = iter(refs)
    x_ref = next(it)
    s_ref = next(it)
    w_ref = next(it)
    wg_ref = next(it) if has_gate else None
    b_ref = next(it) if has_bias else None
    o_ref = next(it)
    l_s = next(it)
    c_s = next(it)
    acc_s = next(it)
    accg_s = next(it) if has_gate else None

    n = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((n == 0) & (j == 0))
    def _init_stat():
        l_s[...] = jnp.zeros(l_s.shape, ACCUM_DTYPE)
        c_s[...] = jnp.zeros(c_s.shape, ACCUM_DTYPE)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros(acc_s.shape, ACCUM_DTYPE)
        if has_gate:
            accg_s[...] = jnp.zeros(accg_s.shape, ACCUM_DTYPE)

    xb = x_ref[...].astype(ACCUM_DTYPE)             # (rt, blk)

    @pl.when(n == 0)
    def _stat():
        # Chained ones-MMA sum of squares of the RAW rows: one
        # (rt, w) x (w, 128) ones-contraction per sub-slice, each
        # landing the sub-slice sum replicated across the 128 lanes.
        w = -(-blk // max(chain, 1))
        l_blk = jnp.zeros(l_s.shape, ACCUM_DTYPE)
        for lo in range(0, blk, w):
            sub = xb[:, lo:lo + w]
            ones = jnp.ones((sub.shape[1], _LANES), ACCUM_DTYPE)
            l_blk = l_blk + jax.lax.dot_general(
                sub * sub, ones, (((1,), (0,)), ((), ())),
                preferred_element_type=ACCUM_DTYPE)

        # Kahan carry across k-blocks (the compensated machinery of
        # kernels/mma_compensated.py, f32 partials per the paper).
        l_old = l_s[...]
        y = l_blk - c_s[...]
        t = l_old + y
        c_s[...] = (t - l_old) - y
        l_s[...] = t

    # Unnormalized matmul partial: the gemma (1 + scale) element scale
    # commutes with the matmul, the per-row rstd does not — it is
    # applied once at the end.
    xs = xb * (1.0 + s_ref[...].astype(ACCUM_DTYPE))
    acc_s[...] = acc_s[...] + jax.lax.dot_general(
        xs, w_ref[...].astype(ACCUM_DTYPE), (((1,), (0,)), ((), ())),
        preferred_element_type=ACCUM_DTYPE)
    if has_gate:
        accg_s[...] = accg_s[...] + jax.lax.dot_general(
            xs, wg_ref[...].astype(ACCUM_DTYPE),
            (((1,), (0,)), ((), ())),
            preferred_element_type=ACCUM_DTYPE)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        ms = (l_s[:, 0:1] - c_s[:, 0:1]) / d
        rstd = jax.lax.rsqrt(ms + eps)
        up = acc_s[...] * rstd
        if has_bias:
            up = up + b_ref[...].astype(ACCUM_DTYPE)
        if has_gate:
            up = _apply_act(accg_s[...] * rstd, act) * up
        o_ref[...] = up.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "eps", "act", "has_gate", "has_bias", "chain", "block_rows",
    "interpret"))
def _nm_call(x2d, scale2d, w, *opt, eps, act, has_gate, has_bias,
             chain, block_rows, interpret):
    rows, d = x2d.shape
    dout = w.shape[1]
    blk = max(_LANES, block_rows)
    d_p = _ceil_to(d, blk)
    nkb = d_p // blk
    dout_p = _ceil_to(dout, _LANES)
    # Output tile: the widest multiple of 128 lanes, up to _N_TILE,
    # that divides dout_p.
    tn = max(t for t in range(_LANES, min(_N_TILE, dout_p) + 1, _LANES)
             if dout_p % t == 0)
    rt = max(_ceil_to(min(rows, 128), 8), 8)        # row tile
    rows_p = _ceil_to(rows, rt)

    x_p = jnp.pad(x2d, ((0, rows_p - rows), (0, d_p - d)))
    s_p = jnp.pad(scale2d, ((0, 0), (0, d_p - d)))
    ops = [x_p, s_p]
    in_specs = [
        pl.BlockSpec((rt, blk), lambda i, n, j: (i, j)),
        pl.BlockSpec((1, blk), lambda i, n, j: (0, j)),
    ]
    it = iter(opt)
    for wi in (w, next(it) if has_gate else None):
        if wi is None:
            continue
        ops.append(jnp.pad(wi, ((0, d_p - d), (0, dout_p - dout))))
        in_specs.append(pl.BlockSpec((blk, tn),
                                     lambda i, n, j: (j, n)))
    if has_bias:
        ops.append(jnp.pad(next(it).reshape(1, dout),
                           ((0, 0), (0, dout_p - dout))))
        in_specs.append(pl.BlockSpec((1, tn), lambda i, n, j: (0, n)))

    scratch = [
        pltpu.VMEM((rt, _LANES), ACCUM_DTYPE),      # sum of squares
        pltpu.VMEM((rt, _LANES), ACCUM_DTYPE),      # Kahan carry
        pltpu.VMEM((rt, tn), ACCUM_DTYPE),          # matmul partial
    ]
    if has_gate:
        scratch.append(pltpu.VMEM((rt, tn), ACCUM_DTYPE))

    kernel = functools.partial(
        _nm_kernel, blk=blk, chain=int(chain), d=float(d),
        eps=float(eps), act=act, has_gate=has_gate, has_bias=has_bias)
    out = pl.pallas_call(
        kernel,
        grid=(rows_p // rt, dout_p // tn, nkb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rt, tn), lambda i, n, j: (i, n)),
        out_shape=jax.ShapeDtypeStruct((rows_p, dout_p), x2d.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*ops)
    return out[:rows, :dout]


def mma_norm_matmul(x, scale, w, *, w_gate=None, bias=None, act=None,
                    eps=1e-6, chain=4, block_rows=128, interpret=None):
    """Fused ``rmsnorm(x) @ w``: x (..., d), scale (d,), w (d, dout)
    -> (..., dout) in x.dtype, without materializing the normalized
    activations.

    ``scale`` is the gemma-convention norm weight (the kernel applies
    ``1 + scale``).  ``bias`` (dout,) is added to the plain projection;
    with ``w_gate`` (d, dout) the output is the MLP pair
    ``act(rmsnorm(x) @ w_gate) * (rmsnorm(x) @ w [+ bias])`` — one
    k-walk feeds both projections.  ``act`` is None | 'silu' | 'gelu'.
    ``chain`` / ``block_rows`` are the paper's R and B knobs for the
    in-kernel row statistic and the k-block walk; either accepts
    ``'auto'`` to resolve the engine-restricted tuned plan from the
    autotuner registry (op ``norm_matmul``, engine ``fused_pallas``).
    """
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = int(math.prod(lead)) if lead else 1
    if chain == "auto" or block_rows == "auto":
        from repro.core import autotune
        plan = autotune.get_plan(x.size, x.dtype, op="norm_matmul",
                                 engine="fused_pallas")
        chain = plan.chain if chain == "auto" else chain
        block_rows = plan.block_rows if block_rows == "auto" \
            else block_rows
    opt = ()
    if w_gate is not None:
        opt += (w_gate,)
    if bias is not None:
        opt += (bias,)
    out = _nm_call(
        x.reshape(rows, d), jnp.asarray(scale).reshape(1, d), w, *opt,
        eps=float(eps), act=act, has_gate=w_gate is not None,
        has_bias=bias is not None, chain=int(chain),
        block_rows=int(block_rows),
        interpret=_should_interpret(interpret))
    return out.reshape(*lead, out.shape[-1])

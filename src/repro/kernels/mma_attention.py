"""Fused flash-attention Pallas kernel with in-kernel chained-MMA row
statistics (ROADMAP open item 1; registered as the ``attention`` op's
``fused_pallas`` engine in ``repro.core.dispatch``).

One kernel instance owns a (batch, kv-head, group, query-tile) cell of
the grid and walks the KV sequence in ``block_rows``-sized blocks (the
sequential innermost grid axis).  Per block it computes the score tile
on the MXU, then folds the online-softmax row statistics *inside the
kernel* — the gap Dakkak et al. (arXiv:1811.09736) identify:
reductions fused into the surrounding TCU kernel instead of separate
passes around it:

  * the running **row max** via a chained max-fold over ``chain``
    sub-slices of the block (the max variant of the paper's chain);
  * the **row sum of exponentials** via chained ones-matrix MMAs — one
    ``(rows, w) x (w, 128)`` ones-contraction per sub-slice, f32
    accumulate (``ACCUM_DTYPE``), exactly the paper's reduction
    encoding — combined across blocks with a Kahan carry in VMEM (the
    compensated machinery of ``kernels/mma_compensated.py``);
  * the weighted-value accumulator, rescaled by ``exp(m_old - m_new)``
    per block, all partials f32 per the paper's precision contract.

Covers causal, sliding-window, GQA (grouped queries share one KV
head), per-row decode positions, and the ring-buffer ``kv_len`` mask —
the single-query decode path reads the dense view of the paged
int8+residual KV store (``models/kv_cache.py``).  A fully-masked query
row yields exactly zero output (the all-masked semantics
``models/attention.py`` documents), not NaN.

Runs in ``interpret=True`` off-TPU like every kernel in this package;
see docs/ARCHITECTURE.md for the paper-to-code map.
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

# Additive mask value — matches models/attention.NEG_INF (kept local:
# the model layer imports the dispatch registry, which lazily imports
# this module; a top-level import back into models would be a cycle).
NEG_INF = -2.0e38

# Finite row-max seed: exp(_M_INIT - _M_INIT) == 1 keeps the correction
# factor well-defined for rows that have seen no valid key yet (a -inf
# seed would produce inf - inf -> NaN in the rescale).
_M_INIT = -1.0e30

_LANES = 128     # MXU/VPU lane width: head dims pad to it, the ones
#                  contraction folds onto it
_Q_TILE = 512    # query rows per grid cell


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _attn_kernel(kvlen_ref, q_ref, k_ref, v_ref, qpos_ref, o_ref,
                 m_s, l_s, c_s, acc_s, *, blk, chain, scale, cap,
                 causal, window, has_kvlen, sk):
    j = pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _M_INIT, ACCUM_DTYPE)
        l_s[...] = jnp.zeros(l_s.shape, ACCUM_DTYPE)
        c_s[...] = jnp.zeros(c_s.shape, ACCUM_DTYPE)
        acc_s[...] = jnp.zeros(acc_s.shape, ACCUM_DTYPE)

    q = q_ref[0, 0, 0].astype(ACCUM_DTYPE)          # (tq, hd_p)
    kb = k_ref[0, 0].astype(ACCUM_DTYPE)            # (blk, hd_p)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=ACCUM_DTYPE) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)

    qp = qpos_ref[0]                                # (tq, 1) int32
    kpos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < sk                               # padded keys
    if causal:
        valid &= kpos <= qp
    if window is not None:
        valid &= kpos > qp - window
    if has_kvlen:
        valid &= kpos < kvlen_ref[pl.program_id(0)]
    s = jnp.where(valid, s, NEG_INF)

    # Chained row stats over ``chain`` sub-slices of the block: a
    # max-fold for the running maximum, then one ones-MMA per sub-slice
    # for the row sum of exponentials (each fold lands the sub-slice
    # sum replicated across the 128 output lanes, f32 accumulate).
    w = -(-blk // max(chain, 1))
    m_blk = jnp.full((s.shape[0], 1), _M_INIT, ACCUM_DTYPE)
    for lo in range(0, blk, w):
        m_blk = jnp.maximum(
            m_blk, jnp.max(s[:, lo:lo + w], axis=1, keepdims=True))
    m_old = m_s[...]                                # (tq, LANES)
    m_new = jnp.maximum(m_old, m_blk)
    corr = jnp.exp(m_old - m_new)                   # lane-replicated
    p = jnp.exp(s - m_new[:, 0:1])                  # (tq, blk)
    l_blk = jnp.zeros(l_s.shape, ACCUM_DTYPE)
    for lo in range(0, blk, w):
        sub = p[:, lo:lo + w]
        ones = jnp.ones((sub.shape[1], _LANES), ACCUM_DTYPE)
        l_blk = l_blk + jax.lax.dot_general(
            sub, ones, (((1,), (0,)), ((), ())),
            preferred_element_type=ACCUM_DTYPE)

    # Kahan-carried normaliser across KV blocks: rescale the running
    # sum AND its carry by the correction, then compensated-add the
    # block's chained-MMA partial.
    l_old = l_s[...] * corr
    c_old = c_s[...] * corr
    y = l_blk - c_old
    t = l_old + y
    c_s[...] = (t - l_old) - y
    l_s[...] = t
    m_s[...] = m_new

    vb = v_ref[0, 0].astype(ACCUM_DTYPE)            # (blk, hdv_p)
    acc_s[...] = acc_s[...] * corr[:, 0:1] + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=ACCUM_DTYPE)

    @pl.when(j == pl.num_programs(4) - 1)
    def _finish():
        l = l_s[:, 0:1] - c_s[:, 0:1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o = jnp.where(l > 0.0, acc_s[...] / safe, 0.0)
        o_ref[0, 0, 0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "cap",
                              "has_kvlen", "chain", "block_rows",
                              "interpret"))
def _attn_call(qg, k, v, qpos, kvl, *, causal, window, scale, cap,
               has_kvlen, chain, block_rows, interpret):
    B, Sq, KV, G, hd = qg.shape
    hd_v = v.shape[-1]
    Sk = k.shape[1]
    hd_p = _ceil_to(hd, _LANES)
    hdv_p = _ceil_to(hd_v, _LANES)
    # Query tile: the whole (8-row padded) Sq up to _Q_TILE rows, else
    # _Q_TILE-row tiles, so VMEM use does not grow with the sequence.
    tq = min(max(_ceil_to(Sq, 8), 8), _Q_TILE)
    sq_p = _ceil_to(Sq, tq)
    nq = sq_p // tq
    blk = max(_LANES, block_rows)
    sk_p = _ceil_to(Sk, blk)
    nkb = sk_p // blk

    qg_p = jnp.pad(qg, ((0, 0), (0, sq_p - Sq), (0, 0), (0, 0),
                        (0, hd_p - hd)))
    k_p = jnp.pad(k, ((0, 0), (0, sk_p - Sk), (0, 0), (0, hd_p - hd)))
    v_p = jnp.pad(v, ((0, 0), (0, sk_p - Sk), (0, 0),
                      (0, hdv_p - hd_v)))
    # Padded query rows carry position -1: under a causal mask they see
    # no key at all (sliced off either way).  Positions ride as a
    # (B, Sq_p, 1) column so each (tq, 1) block spans the array's last
    # two dims' tiling; kv_len rides scalar prefetch in SMEM.
    qpos_p = jnp.pad(qpos, ((0, 0), (0, sq_p - Sq)),
                     constant_values=-1)[:, :, None]
    q_t = qg_p.transpose(0, 2, 3, 1, 4)             # (B,KV,G,Sq_p,hd_p)
    k_t = k_p.transpose(0, 2, 1, 3)                 # (B,KV,Sk_p,hd_p)
    v_t = v_p.transpose(0, 2, 1, 3)                 # (B,KV,Sk_p,hdv_p)

    kernel = functools.partial(
        _attn_kernel, blk=blk, chain=int(chain), scale=scale, cap=cap,
        causal=causal, window=window, has_kvlen=has_kvlen, sk=Sk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, G, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, tq, hd_p),
                         lambda b, h, g, i, j, kvl: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, blk, hd_p),
                         lambda b, h, g, i, j, kvl: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk, hdv_p),
                         lambda b, h, g, i, j, kvl: (b, h, j, 0)),
            pl.BlockSpec((1, tq, 1),
                         lambda b, h, g, i, j, kvl: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, tq, hdv_p),
                               lambda b, h, g, i, j, kvl: (b, h, g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, _LANES), ACCUM_DTYPE),     # running max
            pltpu.VMEM((tq, _LANES), ACCUM_DTYPE),     # normaliser
            pltpu.VMEM((tq, _LANES), ACCUM_DTYPE),     # Kahan carry
            pltpu.VMEM((tq, hdv_p), ACCUM_DTYPE),      # value accum
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, sq_p, hdv_p),
                                       v.dtype),
        interpret=interpret,
    )(kvl, q_t, k_t, v_t, qpos_p)
    return out.transpose(0, 3, 1, 2, 4)[:, :Sq, :, :, :hd_v]


def mma_attention(qg, k, v, *, qpos, causal=False, window=None,
                  kv_len=None, scale=None, cap=None, chain=4,
                  block_rows=128, interpret=None):
    """Fused attention: qg (B,Sq,KV,G,hd), k (B,Sk,KV,hd),
    v (B,Sk,KV,hd_v) -> (B,Sq,KV,G,hd_v) in v.dtype.

    ``qpos`` is (Sq,) shared or (B,Sq) per-row absolute positions (the
    continuous-batching decode form); key positions are 0..Sk-1.
    ``kv_len`` (None | scalar | (B,)) masks ring-buffer slots past the
    valid count.  ``cap`` is the optional logit softcap.  ``chain`` /
    ``block_rows`` are the paper's R and B knobs for the in-kernel row
    statistics and the KV block walk; either accepts ``'auto'`` to
    resolve the engine-restricted tuned plan from the autotuner
    registry (op ``attention``, engine ``fused_pallas``).
    """
    B, Sq, KV, G, hd = qg.shape
    Sk = k.shape[1]
    if chain == "auto" or block_rows == "auto":
        from repro.core import autotune
        plan = autotune.get_plan(B * Sq * KV * G * Sk, qg.dtype,
                                 op="attention", engine="fused_pallas")
        chain = plan.chain if chain == "auto" else chain
        block_rows = plan.block_rows if block_rows == "auto" \
            else block_rows
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qpos = jnp.asarray(qpos, jnp.int32)
    if qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos[None, :], (B, Sq))
    if kv_len is None:
        kvl = jnp.full((B,), Sk, jnp.int32)
    else:
        kvl = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(kv_len, jnp.int32)), (B,))
    return _attn_call(
        qg, k, v, qpos, kvl, causal=bool(causal),
        window=None if window is None else int(window),
        scale=float(scale), cap=None if cap is None else float(cap),
        has_kvlen=kv_len is not None, chain=int(chain),
        block_rows=int(block_rows),
        interpret=_should_interpret(interpret))

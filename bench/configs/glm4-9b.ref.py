"""Plain reference of glm4-9b: the published forward pass in float32.

Follows THUDM's GLM-4 modelling code (``modeling_chatglm.py``): RMSNorm
with a weight, pre-norm residual blocks, grouped-query attention with
QKV biases, rotary embedding on the first half of each head's dims in
interleaved pairs, a SwiGLU MLP, a final norm and an untied output
layer.  Weights come from ``bench.checkpoint`` (the bf16 values the
program is served, widened to f32), one layer at a time, so the whole
model never has to fit beside the activations.  Every matmul runs at
``Precision.HIGHEST``: true f32 on a TPU.  It imports nothing of the
program.

``quant="fp8"`` is the control: the same forward with every matmul's
weights and activations rounded to float8 e4m3 (one scale per output
channel of a weight, one per row of an activation), the step below
the bf16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import checkpoint as C
from bench import weights as W

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
QUERY_BLOCK = 512
PAD = 512
ROWS = 128


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w)


def _rope(x, pos, rot, theta):
    """Interleaved rotary on the first ``rot`` dims: pairs (2i, 2i+1)
    turn by pos * theta**(-2i/rot)."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv          # (L, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x[..., :rot].reshape(x.shape[:-1] + (rot // 2, 2))
    a, b = xr[..., 0], xr[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([out.reshape(x.shape[:-1] + (rot,)),
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(x, w, *, cfg_items, quant):
    """One block over one sequence x (L, d), causal."""
    s = dict(cfg_items)
    L = x.shape[0]
    H, KV, hd = s["heads"], s["kv"], s["hd"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(L)
    h = _rms(x, w["attn_norm"], s["eps"])
    q = _mm(h, w["q_w"], quant)
    k = _mm(h, w["k_w"], quant)
    v = _mm(h, w["v_w"], quant)
    if s["bias"]:
        q, k, v = q + w["q_b"], k + w["k_b"], v + w["v_b"]
    q = _rope(q.reshape(L, H, hd), pos, s["rot"], s["theta"])
    k = _rope(k.reshape(L, KV, hd), pos, s["rot"], s["theta"])
    v = v.reshape(L, KV, hd)
    q = q.reshape(L, KV, H // KV, hd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        sc = jnp.einsum("qkgh,ckh->kgqc", qb, k, precision=HI) \
            / np.sqrt(hd)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqc,ckh->qkgh", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(L // QUERY_BLOCK))
    o = o.reshape(L, H * hd)
    x = x + _mm(o, w["o_w"], quant)
    h = _rms(x, w["mlp_norm"], s["eps"])
    g = _mm(h, w["gate_w"], quant)
    u = _mm(h, w["up_w"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["down_w"], quant)


@functools.partial(jax.jit, static_argnames=("shapes",))
def _weights(words, layer, *, shapes):
    return C.tensors(words, shapes, layer)


@functools.partial(jax.jit, static_argnames=("d",))
def _embed(words, tokens, *, d):
    return C.make_rows(words, "embed", tokens, d).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(h, final_norm, head, *, cfg_items, quant):
    s = dict(cfg_items)
    h = _rms(h, final_norm.astype(jnp.float32), s["eps"])
    return _mm(h, head.astype(jnp.float32), quant)


def hidden(cfg: dict, words, tokens: np.ndarray, quant=None):
    """Final-layer hidden states of one token sequence, padded at the
    end to a multiple of PAD rows (causal: padding changes nothing
    before it)."""
    s = C.dims(cfg)
    n = len(tokens)
    L = -(-n // PAD) * PAD
    toks = np.zeros((L,), np.int32)
    toks[:n] = tokens
    items = tuple(sorted(s.items()))
    shapes = tuple(sorted(C.layer_shapes(cfg).items()))
    with jax.default_matmul_precision("highest"):
        x = _embed(words, jnp.asarray(toks), d=s["d"])
        for layer in range(s["layers"]):
            w = _weights(words, jnp.uint32(layer), shapes=shapes)
            x = _layer(x, w, cfg_items=items, quant=quant)
    return x


def served_gaps(cfg: dict, seed: int, seqs, *, control: bool = False):
    """For each ``(tokens, n_prompt)`` in ``seqs``: at every position
    that produced a served token ``tokens[p + 1]`` (p >= n_prompt - 1),
    how far that token's reference logit lies below the reference's
    best.  With ``control``, the token judged at each position is the
    fp8 forward's first choice in place of the served one.  Returns one
    float64 array of gaps per sequence."""
    items = tuple(sorted(C.dims(cfg).items()))
    words = W.seed_words(seed)
    g = tuple(sorted(C.global_shapes(cfg).items()))
    g = _weights(words, jnp.uint32(0), shapes=tuple(
        (n, sh) for n, sh in g if n != "embed"))
    final_norm, head = g["final_norm"], g["head"]
    out = []
    for tokens, n_prompt in seqs:
        tokens = np.asarray(tokens, np.int32)
        rows = np.arange(n_prompt - 1, len(tokens) - 1)
        m = len(rows)
        # Row counts padded to a multiple of ROWS: few compiled shapes.
        rows = np.concatenate([rows, np.full(-m % ROWS, rows[-1])])
        with jax.default_matmul_precision("highest"):
            h = hidden(cfg, words, tokens)[rows]
            ref = _logits(h, final_norm, head, cfg_items=items,
                          quant=None)
            if control:
                hq = hidden(cfg, words, tokens, quant="fp8")[rows]
                pick = jnp.argmax(_logits(hq, final_norm, head,
                                          cfg_items=items, quant="fp8"),
                                  axis=-1)
            else:
                pick = jnp.asarray(tokens[rows + 1])
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out.append(np.asarray(best - got, np.float64)[:m])
        del h, ref
    return out

"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free time-mix with
data-dependent per-channel decay, plus squared-ReLU channel-mix.

Per head (size hs), state S in R^{hs x hs}:
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(decay_base + LoRA(x-shifted))) — the data-dependent
decay that distinguishes Finch from RWKV-5.  w_t lies in (0, 1] by
construction, so the decay exponent is not clipped.

It follows the published Finch forward (RWKV-LM's v6 ``model.py`` and
the HF ``modeling_rwkv6.py``): the five-way ddlerp token shift, the
decay LoRA, the bonus ``u``, a per-head GroupNorm (``ln_x``) with eps
``1e-5 * head_size_divisor**2`` and a SiLU gate; the channel-mix is a
squared-ReLU MLP behind its own token shift and a sigmoid receptance.
The LayerNorm the published model applies to the embeddings before
block 0 (``ln0``) is ``embed_norm`` in ``repro.models.transformer``.
HF's ``rescale_every`` (halving the residual every 6 layers, with the
output weights scaled to match) is an fp16 overflow guard that leaves
the function unchanged, and is not applied.

Named scopes mark the parts in the compiled program: ``rwkv.time_mix``
(the whole time-mix), inside it ``rwkv.wkv`` (the recurrence alone),
and ``rwkv.channel_mix``.

Training runs the WKV recurrence as a lax.scan over time (compile-size
O(1) in sequence length); decode is a single state update.  The state is
the "KV cache" of this family: O(1) in sequence length, which is why the
long_500k cell runs for this arch (see docs/design-notes.md §7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.scan import tc_cumprod
from repro.distributed.sharding import constrain
from repro.models.param import Param

MIX_NAMES = ("w", "k", "v", "r", "g")
# ln_x's eps: 1e-5 * head_size_divisor**2, with head_size_divisor 8 in
# every published Finch configuration.
LN_X_EPS = 1e-5 * 8 ** 2


def timemix_specs(cfg):
    d = cfg.d_model
    r = cfg.rwkv
    n = d // r.head_size
    return {
        "maa_x": Param((d,), (None,), "zeros"),
        "maa_base": Param((5, d), (None, None), "zeros"),
        "maa_w1": Param((d, 5 * r.lora_mix), ("embed", None)),
        "maa_w2": Param((5, r.lora_mix, d), (None, None, "embed")),
        "decay_base": Param((d,), (None,), "normal", scale=1.0),
        "decay_w1": Param((d, r.lora_decay), ("embed", None)),
        "decay_w2": Param((r.lora_decay, d), (None, "embed")),
        "bonus": Param((n, r.head_size), ("heads", None), "normal",
                       scale=0.1),
        "wr": Param((d, d), ("embed", "heads")),
        "wk": Param((d, d), ("embed", "heads")),
        "wv": Param((d, d), ("embed", "heads")),
        "wg": Param((d, d), ("embed", "heads")),
        "wo": Param((d, d), ("heads", "embed")),
        "ln_x_scale": Param((d,), (None,), "ones"),
        "ln_x_bias": Param((d,), (None,), "zeros"),
    }


def chanmix_specs(cfg):
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "maa_k": Param((d,), (None,), "zeros"),
        "maa_r": Param((d,), (None,), "zeros"),
        "wk": Param((d, ff), ("embed", "mlp")),
        "wv": Param((ff, d), ("mlp", "embed")),
        "wr": Param((d, d), ("embed", None)),
    }


def make_state(cfg, batch: int, dtype=jnp.float32):
    d = cfg.d_model
    r = cfg.rwkv
    n = d // r.head_size
    return {
        "wkv": jnp.zeros((batch, n, r.head_size, r.head_size), jnp.float32),
        "x_tm": jnp.zeros((batch, d), dtype),   # last input (time-mix)
        "x_cm": jnp.zeros((batch, d), dtype),   # last input (channel-mix)
    }


def state_axes():
    return {"wkv": ("batch", "heads", None, None),
            "x_tm": ("batch", None), "x_cm": ("batch", None)}


def _shifted(x, x_prev_last):
    """token shift: concat(prev_tail, x[:-1]) along time."""
    return jnp.concatenate([x_prev_last[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(params, x, xx):
    """Finch data-dependent lerp for the 5 mix streams."""
    base = x + xx * params["maa_x"].astype(x.dtype)
    lora = jnp.tanh(base @ params["maa_w1"].astype(x.dtype))
    b, s, _ = lora.shape
    lora = lora.reshape(b, s, 5, -1)
    mods = jnp.einsum("bsfr,frd->fbsd", lora,
                      params["maa_w2"].astype(x.dtype))
    mixes = params["maa_base"].astype(x.dtype)  # (5, d)
    outs = []
    for i in range(5):
        m = mixes[i] + mods[i]
        outs.append(x + xx * m)
    return outs  # xw, xk, xv, xr, xg


def _wkv_scan(r, k, v, w, u, state0, *, unroll_below: int = 64):
    """r,k,v,w: (B, S, N, hs); u: (N, hs); state0: (B, N, hs, hs) f32."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (B, N, hs)
        kv = k_t[..., :, None] * v_t[..., None, :]           # (B,N,hs,hs)
        y = jnp.einsum("bni,bnij->bnj", r_t,
                       S + u[None, :, :, None] * kv)
        S_new = w_t[..., :, None] * S + kv
        return S_new, y

    xs = tuple(jnp.moveaxis(t, 1, 0).astype(jnp.float32)
               for t in (r, k, v, w))
    seq = r.shape[1]
    if seq <= unroll_below:
        # Unrolled (decode + FLOP-accounting compiles: while-loop bodies
        # are counted once by HloCostAnalysis, unrolled ops are exact).
        S, ys = state0, []
        for t in range(seq):
            S, y = step(S, tuple(x[t] for x in xs))
            ys.append(y)
        return jnp.stack(ys, axis=1), S
    state, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1), state                     # (B,S,N,hs)


def _wkv_chunked(r, k, v, w, u, state0, *, chunk: int = 32):
    """Chunk-parallel WKV (§Perf, beyond-paper): the length-S sequential
    recurrence becomes

      1. intra-chunk prefix (from zero state) for ALL chunks in parallel
         (a ``chunk``-step loop over (B, n_chunks, N, hs, hs) tensors);
      2. a length-S/chunk scan propagating chunk boundary states
         S_out = diag(prod w) S_in + S_local;
      3. one batched einsum adding each token's cross-chunk term
         r_t · (prefix-decay_t ⊙ S_in[chunk(t)]).

    The step-3 prefix decays are a log-space triangular-MMA scan
    (``repro.core.scan.tc_cumprod``): w in (0,1) keeps the log-space
    sum monotone and overflow-free, and the products span at most
    ``chunk`` steps, so the result matches the sequential scan to f32
    accumulation tolerance (2e-5 in tests/test_rwkv_chunked.py)."""
    B, S, N, hs = r.shape
    c = chunk
    assert S % c == 0, (S, c)
    nc = S // c
    rf, kf, vf, wf = (t.astype(jnp.float32).reshape(B, nc, c, N, hs)
                      for t in (r, k, v, w))

    # 1. intra-chunk (parallel over chunks)
    s_loc = jnp.zeros((B, nc, N, hs, hs), jnp.float32)
    ys = []
    for t in range(c):
        kv = kf[:, :, t, :, :, None] * vf[:, :, t, :, None, :]
        y = jnp.einsum("bcni,bcnij->bcnj", rf[:, :, t],
                       s_loc + u[None, None, :, :, None] * kv)
        s_loc = wf[:, :, t, :, :, None] * s_loc + kv
        ys.append(y)
    y_intra = jnp.stack(ys, axis=2)                  # (B, nc, c, N, hs)

    # 2. boundary-state scan over chunks
    d_chunk = jnp.prod(wf, axis=2)                   # (B, nc, N, hs)

    def inter(s_in, inp):
        d_i, s_loc_i = inp
        s_out = d_i[..., :, None] * s_in + s_loc_i
        return s_out, s_in                           # emit incoming state

    d_x = jnp.moveaxis(d_chunk, 1, 0)
    l_x = jnp.moveaxis(s_loc, 1, 0)
    s_final, s_in = jax.lax.scan(inter, state0, (d_x, l_x))
    s_in = jnp.moveaxis(s_in, 0, 1)                  # (B, nc, N, hs, hs)

    # 3. cross-chunk contribution via prefix decays: an exclusive
    # cumulative product over the chunk axis, run as a log-space
    # triangular-MMA scan (repro.core.scan) so the prefix rides the
    # matrix unit like every other reduction in the stack.  Geometry
    # sized to the chunk axis (one c x c triangular MMA per chunk, no
    # pad-to-512 waste on this training hot path).
    pref = tc_cumprod(wf, axis=2, inclusive=False, chain=1,
                      m=max(8, min(128, c)))
    y_cross = jnp.einsum("bcsni,bcnij->bcsnj", rf * pref, s_in)
    y = (y_intra + y_cross).reshape(B, S, N, hs)
    return y, s_final


def _group_norm(y, scale, bias, n_heads, eps=LN_X_EPS):
    """Per-head LayerNorm over head_size (RWKV's ln_x)."""
    b, s, d = y.shape
    yh = y.reshape(b, s, n_heads, -1).astype(jnp.float32)
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.var(yh, axis=-1, keepdims=True)
    yh = (yh - mu) * jax.lax.rsqrt(var + eps)
    out = yh.reshape(b, s, d) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return out


@jax.named_scope("rwkv.time_mix")
def time_mix(params, cfg, x, state):
    """x: (B,S,D). state: see make_state. Returns (out, new_state)."""
    dt = x.dtype
    b, s, d = x.shape
    r_cfg = cfg.rwkv
    n = d // r_cfg.head_size

    x_prev = _shifted(x, state["x_tm"].astype(dt))
    xx = x_prev - x
    xw, xk, xv, xr, xg = _ddlerp(params, x, xx)

    decay_mod = jnp.tanh(xw @ params["decay_w1"].astype(dt)) \
        @ params["decay_w2"].astype(dt)
    logw = -jnp.exp(params["decay_base"].astype(jnp.float32)
                    + decay_mod.astype(jnp.float32))
    w = jnp.exp(logw)                                        # (B,S,D) in (0,1]

    r = (xr @ params["wr"].astype(dt)).reshape(b, s, n, -1)
    k = (xk @ params["wk"].astype(dt)).reshape(b, s, n, -1)
    v = (xv @ params["wv"].astype(dt)).reshape(b, s, n, -1)
    g = jax.nn.silu(xg @ params["wg"].astype(dt))
    wh = w.reshape(b, s, n, -1)

    chunk = getattr(cfg, "rwkv_chunk", 0)
    with jax.named_scope("rwkv.wkv"):
        if chunk and s % chunk == 0 and s > chunk:
            y, new_wkv = _wkv_chunked(r, k, v, wh,
                                      params["bonus"].astype(jnp.float32),
                                      state["wkv"], chunk=chunk)
        else:
            y, new_wkv = _wkv_scan(r, k, v, wh,
                                   params["bonus"].astype(jnp.float32),
                                   state["wkv"])
    y = _group_norm(y.reshape(b, s, d), params["ln_x_scale"],
                    params["ln_x_bias"], n)
    out = (y.astype(dt) * g) @ params["wo"].astype(dt)
    new_state = dict(state, wkv=new_wkv, x_tm=x[:, -1, :])
    return constrain(out, ("batch", None, None)), new_state


@jax.named_scope("rwkv.channel_mix")
def channel_mix(params, cfg, x, state):
    dt = x.dtype
    x_prev = _shifted(x, state["x_cm"].astype(dt))
    xx = x_prev - x
    xk = x + xx * params["maa_k"].astype(dt)
    xr = x + xx * params["maa_r"].astype(dt)
    h = jnp.square(jax.nn.relu(xk @ params["wk"].astype(dt)))
    h = constrain(h, ("batch", "seq", "mlp"))
    out = jax.nn.sigmoid(xr @ params["wr"].astype(dt)) \
        * (h @ params["wv"].astype(dt))
    return out, dict(state, x_cm=x[:, -1, :])

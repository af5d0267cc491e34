"""Plain reference of rwkv6-7b: the published Finch forward in float32.

Follows RWKV/v6-Finch-7B-HF's ``modeling_rwkv6.py`` (and RWKV-LM's v6
``model.py``): the embeddings through ``blocks.0.pre_ln`` (RWKV-LM's
``ln0``), then per block ``x += TimeMix(ln1(x))`` and
``x += ChannelMix(ln2(x))``, then ``ln_out`` and the head.  TimeMix is
the five-way ddlerp token shift, the decay
``w_t = exp(-exp(time_decay + tanh(x_w W1) W2))``, the recurrence

    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T),  S_t = diag(w_t) S_{t-1} + k_t v_t^T

with one 64 x 64 float32 state per head run token by token, the
per-head GroupNorm ``ln_x`` (eps ``1e-5 * head_size_divisor**2``), a
SiLU gate and the output projection; ChannelMix is
``sigmoid(x_r W_r) * (relu(x_k W_k)**2 W_v)`` behind its own token
shift.  HF's ``rescale_every`` halvings leave the function unchanged
and are not applied.  Weights come from ``bench.checkpoint_rwkv6`` in
the HF layout and names (the bf16 values the program is served,
widened to f32), made from the seed one layer at a time, so the whole
model never has to fit beside the activations.  Every matmul runs at
``Precision.HIGHEST``: true f32 on a TPU.  It imports nothing of the
program.

Controls, the reference one step below the configuration's precision:
``"fp8"`` (or ``True``) rounds every matmul's weights and activations
to float8 e4m3 (one scale per output channel of a weight, one per row
of an activation); ``"bf16_state"`` keeps the matmuls in f32 and
rounds the WKV state to bf16 after every token.

Besides the logits, the forward gives each layer's WKV state after a
sequence's prompt, which the check compares with the state the
program's store holds after admitting it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import checkpoint_rwkv6 as C
from bench import weights as W

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
PAD = 512
ROWS = 128
CONTROLS = {True: ("fp8", jnp.float32), "fp8": ("fp8", jnp.float32),
            "bf16_state": (None, jnp.bfloat16)}


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """``x @ w`` for ``w`` stored (in, out)."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _linear(x, w, quant):
    """A torch ``Linear`` without bias: ``x @ w.T``, ``w`` (out, in)."""
    return _mm(x, w.T, quant)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _shift(x):
    """The previous token's row, zeros before the first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def wkv(r, k, v, w, u, state_dtype=jnp.float32, n_state=None):
    """The recurrence over one sequence, token by token.  r, k, v, w:
    (L, H, hs); u: (H, hs).  The state S[h, i, j] pairs key dim i with
    value dim j and starts at zero; with ``state_dtype`` bf16 it is
    rounded after each token.  Returns y and, in f32, the state after
    the first ``n_state`` tokens (all of them if None)."""
    H, hs = u.shape
    n_state = r.shape[0] if n_state is None else n_state

    def step(carry, inp):
        S, kept = carry
        t, r_t, k_t, v_t, w_t = inp
        kv = k_t[:, :, None] * v_t[:, None, :]
        y = jnp.einsum("hi,hij->hj", r_t,
                       S.astype(jnp.float32) + u[:, :, None] * kv,
                       precision=HI)
        S = (w_t[:, :, None] * S.astype(jnp.float32) + kv)
        S = S.astype(state_dtype)
        return (S, jnp.where(t == n_state - 1, S, kept)), y

    S0 = jnp.zeros((H, hs, hs), state_dtype)
    (_, kept), y = jax.lax.scan(step, (S0, S0),
                                (jnp.arange(r.shape[0]), r, k, v, w))
    return y, kept.astype(jnp.float32)


def _time_mix(x, w, s, quant, state_dtype, n_state):
    L, d = x.shape
    H, hs, a = s["heads"], s["hs"], "attention."
    xx = _shift(x) - x
    xxx = x + xx * w[a + "time_maa_x"].reshape(d)
    lora = jnp.tanh(_mm(xxx, w[a + "time_maa_w1"], quant))
    lora = lora.reshape(L, 5, s["mix"])
    w2 = w[a + "time_maa_w2"]
    mixed = {}
    for f, c in enumerate(C.STREAMS):
        m = w[a + f"time_maa_{c}"].reshape(d) + _mm(lora[:, f], w2[f],
                                                     quant)
        mixed[c] = x + xx * m
    r = _linear(mixed["r"], w[a + "receptance.weight"], quant)
    k = _linear(mixed["k"], w[a + "key.weight"], quant)
    v = _linear(mixed["v"], w[a + "value.weight"], quant)
    g = jax.nn.silu(_linear(mixed["g"], w[a + "gate.weight"], quant))
    td = w[a + "time_decay"].reshape(d) + _mm(
        jnp.tanh(_mm(mixed["w"], w[a + "time_decay_w1"], quant)),
        w[a + "time_decay_w2"], quant)
    decay = jnp.exp(-jnp.exp(td))
    heads = lambda t: t.reshape(L, H, hs)  # noqa: E731
    y, state = wkv(heads(r), heads(k), heads(v), heads(decay),
                   w[a + "time_faaaa"], state_dtype, n_state)
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + s["gn_eps"])).reshape(L, d)
    y = y * w[a + "ln_x.weight"] + w[a + "ln_x.bias"]
    return _linear(y * g, w[a + "output.weight"], quant), state


def _channel_mix(x, w, s, quant):
    d, f = x.shape[-1], "feed_forward."
    xx = _shift(x) - x
    xk = x + xx * w[f + "time_maa_k"].reshape(d)
    xr = x + xx * w[f + "time_maa_r"].reshape(d)
    h = jnp.square(jax.nn.relu(_linear(xk, w[f + "key.weight"], quant)))
    return jax.nn.sigmoid(_linear(xr, w[f + "receptance.weight"], quant)) \
        * _linear(h, w[f + "value.weight"], quant)


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "quant", "state_dtype"))
def _layer(x, w, n_state, *, cfg_items, quant, state_dtype):
    """One block over one sequence x (L, d); also its WKV state after
    the first ``n_state`` tokens."""
    s = dict(cfg_items)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    dx, state = _time_mix(_ln(x, w["ln1.weight"], w["ln1.bias"], s["eps"]),
                          w, s, quant, state_dtype, n_state)
    x = x + dx
    return x + _channel_mix(_ln(x, w["ln2.weight"], w["ln2.bias"],
                                s["eps"]), w, s, quant), state


@functools.partial(jax.jit, static_argnames=("shapes", "cfg_items"))
def _weights(words, layer, *, shapes, cfg_items):
    return C.tensors(words, shapes, layer, dict(cfg_items))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(words, tokens, ln_w, ln_b, *, cfg_items):
    s = dict(cfg_items)
    x = C.embedding_rows(words, tokens, s).astype(jnp.float32)
    return _ln(x, ln_w.astype(jnp.float32), ln_b.astype(jnp.float32),
               s["eps"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(h, ln_w, ln_b, head, *, cfg_items, quant):
    s = dict(cfg_items)
    h = _ln(h, ln_w.astype(jnp.float32), ln_b.astype(jnp.float32),
            s["eps"])
    return _linear(h, head.astype(jnp.float32), quant)


def _globals(cfg: dict, words):
    items = tuple(sorted(C.dims(cfg).items()))
    shapes = tuple(sorted(C.global_shapes(cfg).items()))
    return _weights(words, jnp.uint32(0), cfg_items=items, shapes=tuple(
        (n, sh) for n, sh in shapes if n != "embeddings.weight"))


def hidden(cfg: dict, words, tokens: np.ndarray, quant=None,
           state_dtype=jnp.float32, g=None, n_state=None) -> tuple:
    """Final-layer hidden states (before ``ln_out``) of one token
    sequence, padded at the end to a multiple of PAD rows (causal:
    padding changes nothing before it), and each layer's WKV state
    after its first ``n_state`` tokens (all of them if None), stacked
    (layers, heads, hs, hs) in f32."""
    s = C.dims(cfg)
    items = tuple(sorted(s.items()))
    g = _globals(cfg, words) if g is None else g
    n = len(tokens)
    L = -(-n // PAD) * PAD
    toks = np.zeros((L,), np.int32)
    toks[:n] = tokens
    shapes = tuple(sorted(C.layer_shapes(cfg).items()))
    n_state = jnp.int32(n if n_state is None else n_state)
    states = []
    with jax.default_matmul_precision("highest"):
        x = _embed(words, jnp.asarray(toks), g["blocks.0.pre_ln.weight"],
                   g["blocks.0.pre_ln.bias"], cfg_items=items)
        for layer in range(s["layers"]):
            w = _weights(words, jnp.uint32(layer), shapes=shapes,
                         cfg_items=items)
            x, state = _layer(x, w, n_state, cfg_items=items, quant=quant,
                              state_dtype=state_dtype)
            states.append(state)
    return x, jnp.stack(states)


def logits(cfg: dict, seed: int, tokens, quant=None,
           state_dtype=jnp.float32) -> np.ndarray:
    """(len(tokens), vocab) f32 logits of the full forward at every
    position of one sequence."""
    words = W.seed_words(seed)
    g = _globals(cfg, words)
    h, _ = hidden(cfg, words, np.asarray(tokens, np.int32), quant,
                  state_dtype, g)
    items = tuple(sorted(C.dims(cfg).items()))
    with jax.default_matmul_precision("highest"):
        out = _logits(h[:len(tokens)], g["ln_out.weight"],
                      g["ln_out.bias"], g["head.weight"], cfg_items=items,
                      quant=quant)
    return np.asarray(out, np.float32)


def served_gaps(cfg: dict, seed: int, seqs, *, control=False,
                states=False):
    """For each ``(tokens, n_prompt)`` in ``seqs``: at every position
    that produced a served token ``tokens[p + 1]`` (p >= n_prompt - 1),
    how far that token's reference logit lies below the reference's
    best.  With ``control`` (``True`` or ``"fp8"``, or
    ``"bf16_state"``), the token judged at each position is that
    control's first choice in place of the served one.  Returns one
    float64 array of gaps per sequence; with ``states``, also the WKV
    state (layers, heads, hs, hs) after the prompt of each, as the
    reference holds it (the control, with ``control``)."""
    items = tuple(sorted(C.dims(cfg).items()))
    words = W.seed_words(seed)
    g = _globals(cfg, words)
    quant, state_dtype = CONTROLS[control] if control else (
        None, jnp.float32)
    out, held = [], []
    for tokens, n_prompt in seqs:
        tokens = np.asarray(tokens, np.int32)
        rows = np.arange(n_prompt - 1, len(tokens) - 1)
        m = len(rows)
        # Row counts padded to a multiple of ROWS: few compiled shapes.
        rows = np.concatenate([rows, np.full(-m % ROWS, rows[-1])])

        def head(h, q):
            return _logits(h, g["ln_out.weight"], g["ln_out.bias"],
                           g["head.weight"], cfg_items=items, quant=q)

        with jax.default_matmul_precision("highest"):
            h, state = hidden(cfg, words, tokens, g=g, n_state=n_prompt)
            ref = head(h[rows], None)
            if control:
                hc, state = hidden(cfg, words, tokens, quant, state_dtype,
                                   g, n_prompt)
                pick = jnp.argmax(head(hc[rows], quant), axis=-1)
            else:
                pick = jnp.asarray(tokens[rows + 1])
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out.append(np.asarray(best - got, np.float64)[:m])
        held.append(np.asarray(state, np.float32))
        del ref
    return (out, held) if states else out

"""The seeded checkpoint of RWKV-6 "Finch", in the HF layout.

Tensor names and layouts are those of RWKV/v6-Finch-7B-HF
(``modeling_rwkv6.py``), without the ``rwkv.`` prefix: per block
``ln1`` / ``ln2``, ``attention.time_maa_{x,w,k,v,r,g}`` (1, 1, d),
``attention.time_maa_w1`` (d, 5 m), ``attention.time_maa_w2``
(5, m, d), ``attention.time_decay`` (1, 1, d),
``attention.time_decay_w1`` (d, R), ``attention.time_decay_w2``
(R, d), ``attention.time_faaaa`` (heads, head_size), the linear layers
``attention.{receptance,key,value,gate,output}.weight`` and
``feed_forward.{key,receptance,value}.weight`` as torch stores them
(out, in), ``attention.ln_x``, ``feed_forward.time_maa_{k,r}``; and
``embeddings.weight`` (V, d), ``blocks.0.pre_ln`` (RWKV-LM's ``ln0``),
``ln_out`` and ``head.weight`` (V, d).  Every value is stored in bf16.

Values follow RWKV-LM's v6 initialisation where it is not zero, so
that the state keeps a trained model's long memory: the token-shift
mixes ``1 - (i/d)**(1 - l/n)`` (and their v, r, g variants), the
decay ``-6 + 5 (i/(d-1))**(0.7 + 1.3 l/(n-1))`` and the bonus
``l/(n-1) (1 - i/(d-1)) + 0.1 ((i+1) % 3 - 1)``, for channel ``i`` of
layer ``l`` of the published ``n`` layers.  The rest is drawn from the
seed by ``bench.weights``: matrices uniform with standard deviation
0.02 (the LoRAs' first matrices, zero in RWKV-LM, too, so that the
ddlerp and the decay depend on the data), the LoRAs' second matrices
uniform in [-0.01, 0.01) as RWKV-LM draws them, LayerNorm weights
``1 + g`` and biases ``g`` with ``g`` uniform in [-0.1, 0.1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

MATRIX_STD = 0.02
LORA_OUT_HALF = 0.01
NORM_SPREAD = 0.1
STORED = jnp.bfloat16
STREAMS = "wkvrg"


def dims(cfg: dict) -> dict:
    """Sizes under short names, from the keys of RWKV/v6-Finch-7B-HF's
    ``config.json`` and the two LoRA widths of its modelling code."""
    d = int(cfg["hidden_size"])
    if int(cfg["attention_hidden_size"]) != d:
        raise ValueError("attention_hidden_size differs from hidden_size")
    hs = int(cfg["head_size"])
    return {"d": d, "hs": hs, "heads": d // hs,
            "ff": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "init_layers": int(cfg["published"]["num_hidden_layers"]),
            "mix": int(cfg["time_mix_extra_dim"]),
            "decay": int(cfg["time_decay_extra_dim"]),
            "eps": float(cfg["layer_norm_epsilon"]),
            "gn_eps": 1e-5 * float(cfg["head_size_divisor"]) ** 2}


def layer_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, ff, hs, m, R = s["d"], s["ff"], s["hs"], s["mix"], s["decay"]
    out = {"ln1.weight": (d,), "ln1.bias": (d,),
           "ln2.weight": (d,), "ln2.bias": (d,)}
    a = "attention."
    for c in "x" + STREAMS:
        out[a + f"time_maa_{c}"] = (1, 1, d)
    out.update({
        a + "time_maa_w1": (d, 5 * m), a + "time_maa_w2": (5, m, d),
        a + "time_decay": (1, 1, d), a + "time_decay_w1": (d, R),
        a + "time_decay_w2": (R, d), a + "time_faaaa": (d // hs, hs),
        a + "receptance.weight": (d, d), a + "key.weight": (d, d),
        a + "value.weight": (d, d), a + "gate.weight": (d, d),
        a + "output.weight": (d, d),
        a + "ln_x.weight": (d,), a + "ln_x.bias": (d,),
        "feed_forward.time_maa_k": (1, 1, d),
        "feed_forward.time_maa_r": (1, 1, d),
        "feed_forward.key.weight": (ff, d),
        "feed_forward.receptance.weight": (d, d),
        "feed_forward.value.weight": (d, ff)})
    return out


def global_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d = s["d"]
    return {"embeddings.weight": (s["vocab"], d),
            "blocks.0.pre_ln.weight": (d,), "blocks.0.pre_ln.bias": (d,),
            "ln_out.weight": (d,), "ln_out.bias": (d,),
            "head.weight": (s["vocab"], d)}


@functools.lru_cache(maxsize=None)
def _rule_table(name: str, d: int, n: int):
    """RWKV-LM's per-channel values of tensor ``name`` for each of the
    published ``n`` layers, (n, d) in f32 rounded to the stored bf16,
    computed on the host in f64 (so every program that reads them
    reads the same numbers); None for a tensor drawn from the seed."""
    i = np.arange(d, dtype=np.float64)
    lf = np.arange(n, dtype=np.float64)[:, None]
    r0 = lf / (n - 1)                 # 0 to 1 over the published depth
    r1 = 1.0 - lf / n                 # 1 to almost 0
    ddd = i / d
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("time_maa_x", "time_maa_w", "time_maa_k"):
        v = 1.0 - ddd ** r1
    elif leaf == "time_maa_v":
        v = 1.0 - (ddd ** r1 + 0.3 * r0)
    elif leaf in ("time_maa_r", "time_maa_g"):
        v = 1.0 - ddd ** (r1 if name.startswith("feed_forward.")
                          else 0.5 * r1)
    elif leaf == "time_decay":
        v = -6.0 + 5.0 * (i / (d - 1)) ** (0.7 + 1.3 * r0)
    elif leaf == "time_faaaa":
        v = r0 * (1.0 - i / (d - 1)) + 0.1 * ((i + 1) % 3 - 1)
    else:
        return None
    v = np.broadcast_to(v, (n, d)).astype(np.float32)
    return v.astype(STORED).astype(np.float32)


def _half_width(name: str) -> float:
    if name.endswith("_w2"):
        return LORA_OUT_HALF
    if name.endswith(("ln1.weight", "ln2.weight", "ln_x.weight",
                      "pre_ln.weight", "ln_out.weight", ".bias")):
        return NORM_SPREAD
    return MATRIX_STD * math.sqrt(3.0)


def draw(words, name: str, index, layer, s: dict):
    """The stored (bf16) value of elements ``index`` (uint32, flat in
    the HF layout) of tensor ``name`` of layer ``layer`` (may be
    traced); ``words`` is ``bench.weights.seed_words(seed)``, ``s``
    is ``dims(cfg)``."""
    table = _rule_table(name, s["d"], s["init_layers"])
    if table is not None:
        # The channel is the flat index: (1, 1, d) and (heads, hs).
        return jnp.asarray(table)[jnp.asarray(layer, jnp.int32),
                                  jnp.asarray(index, jnp.int32)
                                  ].astype(STORED)
    key = W.tensor_key(words, name, layer)
    u = np.float32(_half_width(name)) * W.uniform(key, index)
    if name.endswith(".weight") and _half_width(name) == NORM_SPREAD:
        u = 1.0 + u
    return u.astype(STORED)


def tensors(words, shapes, layer, s: dict):
    """``{name: tensor}`` for ``shapes`` (pairs of name and shape) in
    the HF layout, as stored; ``layer`` may be traced."""
    return {n: draw(words, n, W.flat_index(tuple(sh)), layer, s)
            for n, sh in shapes}


def embedding_rows(words, rows, s: dict):
    """Rows ``rows`` (int array) of ``embeddings.weight``, as stored:
    the lookup without the table."""
    d = s["d"]
    rows = jnp.asarray(rows, jnp.uint32)
    idx = rows[..., None] * jnp.uint32(d) \
        + jax.lax.broadcasted_iota(jnp.uint32, rows.shape + (d,),
                                   rows.ndim)
    return draw(words, "embeddings.weight", idx, 0, s)

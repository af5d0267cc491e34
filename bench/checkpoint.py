"""The seeded checkpoint of a dense GQA decoder, in its reference layout.

This is what a published checkpoint would be: one set of named tensors
per layer (``x @ W`` layouts, heads concatenated, rotary pairs
interleaved as in THUDM's GLM code), stored in bf16.  Every element is
drawn by ``bench.weights`` from ``(seed, name, layer, index)``, so the
program's loader and the plain reference read the same numbers without
sharing an array.

Scales: matrices uniform with standard deviation 0.02 (the published
``initializer_range`` of the GLM configs), QKV biases with 0.1, and
norm weights ``1 + g`` with ``g`` uniform in [-0.1, 0.1).  The stored
value of a norm is ``g``; its weight is ``1 + g``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

MATRIX_STD = 0.02
BIAS_STD = 0.1
NORM_SPREAD = 0.1
STORED = jnp.bfloat16


def dims(cfg: dict) -> dict:
    """Sizes under the names the checkpoint uses, from the keys of
    THUDM/glm-4-9b's ``config.json``."""
    return {"d": int(cfg["hidden_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv": int(cfg["multi_query_group_num"]),
            "hd": int(cfg["kv_channels"]),
            "ff": int(cfg["ffn_hidden_size"]),
            "vocab": int(cfg["padded_vocab_size"]),
            "layers": int(cfg["num_layers"]),
            "rot": int(cfg["kv_channels"] * cfg["partial_rotary_factor"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["layernorm_epsilon"]),
            "bias": bool(cfg["add_qkv_bias"])}


def layer_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, q, kv, ff = s["d"], s["heads"] * s["hd"], s["kv"] * s["hd"], s["ff"]
    out = {"attn_norm": (d,), "q_w": (d, q), "k_w": (d, kv),
           "v_w": (d, kv), "o_w": (q, d), "mlp_norm": (d,),
           "gate_w": (d, ff), "up_w": (d, ff), "down_w": (ff, d)}
    if s["bias"]:
        out.update(q_b=(q,), k_b=(kv,), v_b=(kv,))
    return out


def global_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    return {"embed": (s["vocab"], s["d"]), "final_norm": (s["d"],),
            "head": (s["d"], s["vocab"])}


def scale(name: str) -> float:
    """Half-width of the uniform draw of tensor ``name``."""
    if name.endswith("norm"):
        return NORM_SPREAD
    if name.endswith("_b"):
        return BIAS_STD * math.sqrt(3.0)
    return MATRIX_STD * math.sqrt(3.0)


def draw(words, name: str, index, layer=0):
    """The stored (bf16) value of elements ``index`` of ``name``;
    ``words`` is ``bench.weights.seed_words(seed)``."""
    key = W.tensor_key(words, name, layer)
    return (np.float32(scale(name)) * W.uniform(key, index)).astype(STORED)


def tensor(words, name: str, shape, layer=0):
    """A whole tensor in its reference layout, as stored."""
    return draw(words, name, W.flat_index(tuple(shape)), layer)


def tensors(words, shapes, layer=0):
    """``{name: tensor}`` for ``shapes`` (pairs of name and shape);
    ``layer`` may be traced."""
    return {n: tensor(words, n, sh, layer) for n, sh in shapes}


def rotary_source(j, hd: int, rot: int):
    """For a head dim ``j`` of a layout that rotates the pairs
    (j, j + rot/2) of its first ``rot`` dims, the dim of the interleaved
    layout (pairs (2i, 2i + 1)) that holds the same feature."""
    half = rot // 2
    j = jnp.asarray(j, jnp.uint32)
    return jnp.where(j < half, 2 * j,
                     jnp.where(j < rot, 2 * (j - half) + 1, j))


def make_rows(words, name: str, rows, width: int):
    """Rows ``rows`` (int array) of a 2-D tensor ``name`` of ``width``
    columns, as stored: an embedding lookup without the table."""
    rows = jnp.asarray(rows, jnp.uint32)
    idx = rows[..., None] * jnp.uint32(width) \
        + jax.lax.broadcasted_iota(jnp.uint32, rows.shape + (width,),
                                   rows.ndim)
    return draw(words, name, idx)

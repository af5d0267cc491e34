"""Weights and data made from the seed by a counter hash.

Element ``i`` of tensor ``name`` (of layer ``layer``) is a pure function
of ``(seed, name, layer, i)``: an integer hash of the element's index in
the tensor's *reference* layout.  So the program's copy, stacked and
permuted into the program's own layout, and the plain reference's copy,
made one layer at a time, hold the same numbers, and neither takes
anything from the other.  The hash is elementwise, so XLA fuses it into
the one pass that writes each tensor.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLD = np.uint32(0x9E3779B9)


def _mix(x):
    """lowbias32: a bijective avalanche hash of uint32 words."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def seed_words(seed: int) -> np.ndarray:
    """The two uint32 words of a seed of up to 64 bits, as the array
    every draw takes (traced, so one compiled program serves every
    seed)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def tensor_key(words, name: str, layer=0):
    """uint32 key of one tensor from ``seed_words``; ``layer`` may be
    traced (an iota over a stacked layer axis gives one key per
    layer)."""
    words = jnp.asarray(words, jnp.uint32)
    tag = np.uint32(zlib.crc32(name.encode()))
    k = _mix(words[0] ^ tag)
    k = _mix(k + words[1] * _GOLD)
    return _mix(k + jnp.asarray(layer, jnp.uint32) * _GOLD)


def uniform(key, index):
    """f32 in [-1, 1) from uint32 ``index`` (any shape) and ``key``."""
    h = _mix(_mix(index.astype(jnp.uint32) ^ key) + key * _GOLD)
    return (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -23) - 1.0


def flat_index(shape):
    """uint32 row-major index of every element of ``shape``."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + jnp.uint32(stride) * jax.lax.broadcasted_iota(
            jnp.uint32, shape, ax)
        stride *= shape[ax]
    if stride >= 1 << 32:
        raise ValueError(f"tensor of {stride} elements: more than a "
                         f"uint32 index holds")
    return idx


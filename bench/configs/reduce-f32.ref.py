"""Plain reference of reduce-f32: the sums in float64 on the host.

The number compared is an answer's error as a share of the sum of its
terms' magnitudes, ``|got - want| / sum|term|`` (for ``squared_sum``
the terms are ``x * x``, so the share is a relative error): the
standard measure of a summation's accuracy, which stays finite where
signed data cancels to a small sum.

``control`` is the reference put in the program's place one precision
below the configuration's float32: plain ``jnp`` sums of the inputs
rounded to bfloat16, with float32 products and accumulation, on the
device (what a matrix unit at its default precision does to float32
multiplicands).  It imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 24


def reference(op: str, x: np.ndarray) -> tuple:
    """``(sum, sum of |terms|)`` of ``sum(x)`` or ``sum(x * x)`` in
    float64, a block at a time."""
    total = scale = 0.0
    for i in range(0, x.size, CHUNK):
        c = x[i:i + CHUNK].astype(np.float64)
        if op == "reduce_sum":
            total += float(c.sum())
            scale += float(np.abs(c).sum())
        else:
            sq = float(c @ c)
            total += sq
            scale += sq
    return total, scale


def control(op: str, x):
    """The sum with bfloat16 inputs and float32 accumulation, as a
    device scalar (read back by the caller, as the program's is)."""
    xb = jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.sum(xb) if op == "reduce_sum" else jnp.sum(xb * xb)


def err_pct(got: float, want: tuple) -> float:
    """|got - sum| as a percentage of the sum of |terms|."""
    total, scale = want
    return 100.0 * abs(float(got) - total) / max(scale, 1e-300)

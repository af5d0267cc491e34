"""Shared benchmark utilities.

These drivers time whatever backend JAX runs on, and every CSV records
it.  On the CPU (where the tests run, with the Pallas kernels in
interpret mode) the numbers time XLA-CPU and the Pallas interpreter:
they exercise the harness and say nothing about speed on a TPU.  On a
TPU host the same drivers run the compiled kernels; ``chip_smoke.py``
at the repo root is the check that the main path runs there.  The
model-unit numbers (the PRAM cost model in ``core.theory``, HLO
op/flop accounting) and the precision experiments hold on any backend.
"""

from __future__ import annotations

import time

import jax


def time_us(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.2f},{derived}")

"""Whole-step model FLOP utilisation: the FLOPs the window's tokens
needed (``bench/flops.py``: prefills of its admissions, decode of every
later token), over the window, over the chip's bf16 peak."""

from bench import flops


def read(ctx):
    return flops.mfu_pct(ctx)

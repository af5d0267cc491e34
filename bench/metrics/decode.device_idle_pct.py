"""Share of the traced window in which no operation ran on the device:
1 - the union of op intervals over the window, averaged over the chips
used."""

from bench import trace as T


def read(ctx):
    return T.idle_pct(ctx["trace"])
